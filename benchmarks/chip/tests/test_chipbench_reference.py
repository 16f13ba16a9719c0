"""The plain float32 reference against the program's full-sequence
forward, at a tiny size on the CPU, for both configurations."""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import tiny_cell

# mistral-nemo keeps n_heads * head_dim != d_model at the tiny size too
@pytest.mark.parametrize("cell,head_dim", [("qwen2-7b.chat", 32),
                                           ("mistral-nemo-12b.chat", 48)])
def test_reference_matches_program_forward(cell, head_dim):
    from repro.models.config import ModelConfig
    from repro.models.model import forward
    c = tiny_cell(cell)
    c.conf["head_dim"] = head_dim
    fam, d = c.family, c.family.dims(c.conf)
    key = fam.seed_key(2**33 + 7)
    params = jax.jit(functools.partial(fam.program_params, d))(key)
    # the program in float32 on the very weights served in bf16
    mc = dataclasses.replace(
        ModelConfig(name=c.config_name, **fam.program_config(c.conf)),
        dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    toks = np.random.default_rng(0).integers(0, d.V, 700).astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(forward(mc, p32, {"tokens": jnp.asarray(toks)[None]},
                                  mode="train")[0])[0]
    rows = np.arange(0, 700, 7)
    got = fam.reference_logits(d, key, [toks], [rows], row_len=1024)[0]
    scale = np.abs(want[rows]).max()
    assert np.abs(got - want[rows]).max() <= 1e-4 * scale
    assert (got.argmax(-1) == want[rows].argmax(-1)).all()


def test_control_computes_in_lower_precision():
    c = tiny_cell()
    fam, d = c.family, c.family.dims(c.conf)
    key = fam.seed_key(3)
    toks = np.random.default_rng(1).integers(0, d.V, 300).astype(np.int32)
    rows = np.arange(300)
    ref = fam.reference_logits(d, key, [toks], [rows], row_len=1024)[0]
    ctl = fam.reference_logits(d, key, [toks], [rows], row_len=1024,
                               quant="fp8")[0]
    err = np.abs(ctl - ref).max() / np.abs(ref).max()
    assert 1e-3 < err < 0.5          # fp8 operands: a few percent, not 0


def test_stacked_weights_equal_one_layer_at_a_time():
    c = tiny_cell()
    fam, d = c.family, c.family.dims(c.conf)
    key = fam.seed_key(5)
    stacked = jax.jit(functools.partial(fam.program_params, d))(key)
    for i in range(d.L):
        one = jax.jit(lambda k: fam.layer_weights(d, k, i))(key)
        got = jax.tree.map(lambda x: x[i], stacked["layers"][0])
        assert jax.tree.all(jax.tree.map(
            lambda a, b: bool((a == b).all()), got, one))
    assert fam.seed_key(2**40 + 1).shape == (2,)
    assert not (fam.seed_key(2**32 + 1) == fam.seed_key(1)).all()


def test_packed_rows_equal_one_sequence_at_a_time():
    c = tiny_cell()
    fam, d = c.family, c.family.dims(c.conf)
    key = fam.seed_key(9)
    rng = np.random.default_rng(2)
    seqs = [rng.integers(0, d.V, n).astype(np.int32) for n in (600, 300, 90)]
    rows = [np.arange(len(s) - 40, len(s)) for s in seqs]
    packed = fam.reference_logits(d, key, seqs, rows, row_len=1024)
    assert fam.pack([600, 300, 90], 1024)[1] == 1
    for s, r, got in zip(seqs, rows, packed):
        alone = fam.reference_logits(d, key, [s], [r], row_len=1024)[0]
        assert np.abs(got - alone).max() <= 1e-5 * np.abs(alone).max()
