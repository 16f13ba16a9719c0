"""Legacy-parity + new-device tests for the ``repro.arch`` capability layer.

The refactor's no-regression harness: every (gpu, instr) pair in the old
``MI200_CYCLES``/``MI300_CYCLES`` tables must yield identical cycles,
peaks, and supported-instruction sets through the new ``DeviceSpec`` path —
including under ``mfma_scale`` overlays — and the newly registered devices
must be usable end-to-end by ``scoreboard.simulate`` and
``hlo_bridge.predict``.
"""

import pytest

from repro.arch import (DeviceSpec, Overlay, get_device, list_devices,
                        overlay_grid)
from repro.arch.registry import MI200_CYCLES, MI300_CYCLES
from repro.core import isa
from repro.core.hlo_bridge import best_instr, predict_dots, DotOp
from repro.core.machine import as_machine, get_machine
from repro.core.program import mfma
from repro.core.scoreboard import simulate_program
from repro.core.whatif import scale_table

LEGACY_TABLES = {"mi200": MI200_CYCLES, "mi300": MI300_CYCLES}
SCALES = (0.25, 0.5, 1.0, 1.5, 2.0, 3.7)


# ---------------------------------------------------------------------------
# Legacy parity: cycles, supported sets, peaks
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("gpu", ["mi200", "mi300"])
def test_cycles_parity_all_instructions(gpu):
    spec = get_device(gpu)
    legacy = LEGACY_TABLES[gpu]
    assert set(spec.cycle_table) == set(legacy)
    for name, (cycles, validated) in legacy.items():
        entry = spec.cycle_table[name]
        assert entry.cycles == cycles, name
        assert entry.validated == validated, name
        if not isa.lookup(name).gpr_idx_mode:
            assert spec.mfma_cycles(name) == cycles
            assert isa.mfma_cycles(gpu, name) == cycles


@pytest.mark.parametrize("gpu", ["mi200", "mi300"])
@pytest.mark.parametrize("scale", SCALES)
def test_cycles_parity_under_scale(gpu, scale):
    """The gem5 rounding rule max(1, round(base*scale)) must agree across
    the isa view, the machine facade, and a baked-in overlay."""
    spec = get_device(gpu)
    machine = get_machine(gpu, mfma_scale=scale)
    overlaid = get_machine(gpu).with_overlay(Overlay(mfma_scale=scale))
    for name, (base, _) in LEGACY_TABLES[gpu].items():
        if isa.lookup(name).gpr_idx_mode:
            continue
        expect = max(1, int(round(base * scale)))
        assert isa.mfma_cycles(gpu, name, mfma_scale=scale) == expect
        assert spec.mfma_cycles(name, mfma_scale=scale) == expect
        assert machine.mfma_cycles(name) == expect
        assert overlaid.mfma_cycles(name) == expect


@pytest.mark.parametrize("gpu", ["mi200", "mi300"])
@pytest.mark.parametrize("validated_only", [False, True])
def test_supported_set_parity(gpu, validated_only):
    spec = get_device(gpu)
    legacy = {name for name, (_, v) in LEGACY_TABLES[gpu].items()
              if (v or not validated_only)
              and not isa.lookup(name).gpr_idx_mode}
    assert set(spec.supported_instructions(
        validated_only=validated_only)) == legacy
    assert set(isa.supported_instructions(
        gpu, validated_only=validated_only)) == legacy


@pytest.mark.parametrize("gpu", ["mi200", "mi300", "tpu_v5e"])
def test_peak_parity(gpu):
    spec = get_device(gpu)
    machine = get_machine(gpu)
    assert machine.matrix_flops_per_cycle == pytest.approx(
        spec.matrix_flops_per_cycle)
    assert machine.peak_matrix_tflops == pytest.approx(
        spec.peak_matrix_tflops)


def test_legacy_isa_table_views():
    """isa.MI200_CYCLES / MI300_CYCLES remain importable in the legacy
    {name: (cycles, validated)} form."""
    assert isa.MI200_CYCLES == MI200_CYCLES
    assert isa.MI300_CYCLES == MI300_CYCLES


# ---------------------------------------------------------------------------
# Error contracts (satellite bugfixes)
# ---------------------------------------------------------------------------

def test_supported_instructions_unknown_gpu_error_contract():
    """supported_instructions raises UnsupportedInstructionError for an
    unknown device, consistently with mfma_cycles (not a bare KeyError)."""
    with pytest.raises(isa.UnsupportedInstructionError):
        isa.supported_instructions("no_such_gpu")
    with pytest.raises(isa.UnsupportedInstructionError):
        isa.mfma_cycles("no_such_gpu", "fp32_16x16x16fp16")


def test_scale_table_tpu_clear_error():
    """scale_table on a table-less (TPU) machine raises a clear
    UnsupportedInstructionError, not KeyError: None."""
    with pytest.raises(isa.UnsupportedInstructionError,
                       match="no MFMA cycle table"):
        scale_table(get_machine("tpu_v5e"))


def test_scale_table_explicit_instrs_still_rejects_tableless():
    with pytest.raises(isa.UnsupportedInstructionError):
        scale_table(get_machine("tpu_v5e"),
                    instr_names=["fp32_16x16x16fp16"])


# ---------------------------------------------------------------------------
# New devices: registered and usable end-to-end
# ---------------------------------------------------------------------------

def test_new_devices_registered():
    assert {"mi300x", "tpu_v5p"} <= set(list_devices())


def test_device_kind_resolves_or_raises():
    """JAX's device_kind names a catalog spec; an unknown kind is an
    error, never a silent default device."""
    from repro.arch import UnknownDeviceError, device_for_kind
    assert device_for_kind("TPU v5 lite").name == "tpu_v5e"
    with pytest.raises(UnknownDeviceError, match="TPU v99"):
        device_for_kind("TPU v99")


def test_mi300x_is_a_delta_of_mi300():
    base, x = get_device("mi300"), get_device("mi300x")
    assert set(x.cycle_table) == set(base.cycle_table)
    for name, entry in x.cycle_table.items():
        assert entry.cycles == base.cycle_table[name].cycles
        # inherited timing is not hardware-validated on the derived part
        assert not entry.validated
    assert x.cu_count > base.cu_count
    assert x.clock_mhz > base.clock_mhz


def test_new_devices_simulate():
    prog = [mfma("fp32_16x16x16fp16", d="d", a="a", b="b", c="d"),
            mfma("fp32_16x16x16fp16", d="d", a="a", b="b", c="d")]
    for dev in ("mi300x",):
        res = simulate_program(dev, prog)  # by-name coercion
        lat = get_machine(dev).mfma_cycles("fp32_16x16x16fp16")
        assert res.records[1].issue - res.records[0].issue == lat


def test_new_devices_predict():
    dot = DotOp(in_dtype="bf16", batch=1, m=256, n=256, k=256)
    t = {}
    for dev in ("mi300", "mi300x", "tpu_v5e", "tpu_v5p"):
        pred = predict_dots(get_machine(dev), [(dot, 1.0)])
        assert pred.total_mfma > 0
        assert pred.mce_time_s > 0
        t[dev] = pred.mce_time_s
    # more CUs at higher clock must be faster on the same table
    assert t["mi300x"] < t["mi300"]
    # v5p sustains a higher clock than v5e at the same MXU count
    assert t["tpu_v5p"] < t["tpu_v5e"]


def test_new_device_best_instr():
    assert best_instr(get_machine("mi300x"), "bf16") is not None
    assert best_instr(as_machine(get_device("tpu_v5p")), "bf16") is None


# ---------------------------------------------------------------------------
# Overlays
# ---------------------------------------------------------------------------

def test_overlay_compose_multiplies():
    ov = Overlay(mfma_scale=2.0).compose(Overlay(mfma_scale=1.5,
                                                 clock_scale=1.2))
    assert ov.mfma_scale == pytest.approx(3.0)
    assert ov.clock_scale == pytest.approx(1.2)


def test_overlay_table_patch():
    m = get_machine("mi300").with_overlay(
        Overlay(table_patches={"fp32_16x16x16fp16": 8}))
    assert m.mfma_cycles("fp32_16x16x16fp16") == 8
    # untouched entries keep their cycles and provenance
    assert m.mfma_cycles("fp64_16x16x4fp64") == 32
    assert m.spec.cycle_table["fp64_16x16x4fp64"].validated
    assert not m.spec.cycle_table["fp32_16x16x16fp16"].validated


def test_overlay_mem_latency_scale():
    m = get_machine("mi200").with_overlay(Overlay(mem_latency_scale=2.0))
    assert m.l1d_latency == 280
    assert m.lds_latency == 130
    # a memory what-if must NOT slow the vector ALU (compute pipe)
    assert m.valu_latency == get_machine("mi200").valu_latency


def test_overlay_reports_effective_mfma_scale():
    """Prediction.mfma_scale must report the scenario's scale whether it
    arrived via the legacy knob or an Overlay."""
    dot = DotOp(in_dtype="bf16", batch=1, m=64, n=64, k=64)
    via_knob = predict_dots(get_machine("mi300", mfma_scale=2.0),
                            [(dot, 1.0)])
    via_overlay = predict_dots(
        get_machine("mi300", overlay=Overlay(mfma_scale=2.0)), [(dot, 1.0)])
    assert via_knob.mfma_scale == via_overlay.mfma_scale == 2.0
    assert via_knob.mce_time_s == pytest.approx(via_overlay.mce_time_s)


def test_overlay_patch_adds_missing_instruction():
    """A table patch for an instruction the device lacks ADDS support
    (hypothesised-new-instruction what-if), mirroring derive()."""
    assert "fp32_16x16x32fp8" not in get_device("mi200").cycle_table
    m = get_machine("mi200").with_overlay(
        Overlay(table_patches={"fp32_16x16x32fp8": 8}))
    assert m.mfma_cycles("fp32_16x16x32fp8") == 8
    assert not m.spec.cycle_table["fp32_16x16x32fp8"].validated


def test_overlay_preserves_machine_field_tweaks():
    """replace()-tweaked machine fields survive an overlay (no silent
    rebuild from the backing spec)."""
    import dataclasses
    m = dataclasses.replace(get_machine("mi200"), cu_count=10)
    out = m.with_overlay(Overlay(clock_scale=2.0))
    assert out.cu_count == 10
    assert out.clock_mhz == pytest.approx(2 * 1801.0)
    # tweaked topology feeds the peak formula too
    assert out.matrix_flops_per_cycle == pytest.approx(
        get_machine("mi200").matrix_flops_per_cycle * 10 / 60)


def test_specless_machine_rejects_non_mfma_overlay():
    """A hand-built MachineModel (no backing spec) cannot silently drop
    overlay knobs it can't honour."""
    from repro.core.machine import MachineModel
    hb = MachineModel(name="hb", gpu_table="mi200", clock_mhz=1801.0)
    assert hb.with_overlay(Overlay(mfma_scale=2.0)).mfma_scale == 2.0
    with pytest.raises(ValueError):
        hb.with_overlay(Overlay(clock_scale=2.0))


def test_overlay_grid_cartesian():
    grid = overlay_grid(mfma_scale=(0.5, 1, 2), clock_scale=(1, 1.2))
    assert len(grid) == 6
    assert len({(o.mfma_scale, o.clock_scale) for o in grid}) == 6


def test_overlay_grid_rejects_unknown_axis():
    with pytest.raises(TypeError):
        overlay_grid(bogus_scale=(1, 2))


def test_overlay_tpu_analytic_scale():
    """mfma_scale overlays reach the MXU analytic path (no cycle table)."""
    dot = DotOp(in_dtype="bf16", batch=1, m=512, n=512, k=512)
    base = predict_dots(get_machine("tpu_v5e"), [(dot, 1.0)]).mce_time_s
    doubled = predict_dots(
        get_machine("tpu_v5e").with_overlay(Overlay(mfma_scale=2.0)),
        [(dot, 1.0)]).mce_time_s
    assert doubled == pytest.approx(2 * base)


# ---------------------------------------------------------------------------
# Registry hygiene
# ---------------------------------------------------------------------------

def test_every_registered_spec_is_valid():
    for name in list_devices():
        spec = get_device(name)
        assert isinstance(spec, DeviceSpec)
        assert spec.clock_mhz > 0
        assert spec.cu_count >= 1 and spec.simd_per_cu >= 1
        assert spec.has_cycle_table or spec.mxu_count > 0
        for instr, entry in spec.cycle_table.items():
            assert instr in isa.MFMA_REGISTRY, (name, instr)
            assert entry.cycles >= 1
            assert isinstance(entry.validated, bool)
