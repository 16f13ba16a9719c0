"""The harness drives a whole run at a tiny size on the CPU (everything
but the look for a chip): sound, it comes out correct; with the timed
path broken underneath, it does not.  Also the traffic's invariants."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench_tiny import tiny_cell, tiny_run
from chipbench import check, traffic

SEED = 2**31 + 77


@pytest.fixture(scope="module")
def cell():
    return tiny_cell()


def test_sound_run_is_correct_and_reports(cell):
    out = tiny_run(cell, SEED, control=True)
    assert out.pop("control_gap") >= 0.0
    assert out.pop("control_correct") in (True, False)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"output_tokens_per_s", "itl_p95_ms",
                                   "ttft_p90_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["device"]["count"] == len(jax.devices())


def _alter_tokens(eng):
    orig = eng._decode

    def decode(p, c, t, tbl, ln):
        logits, toks, pools = orig(p, c, t, tbl, ln)
        return logits, (toks + 1) % logits.shape[-1], pools
    eng._decode = decode


def _state_unchanged(eng):
    orig = eng._decode

    def decode(p, c, t, tbl, ln):
        kept = jax.tree.map(jnp.copy, c)
        logits, toks, _ = orig(p, c, t, tbl, ln)
        return logits, toks, kept
    eng._decode = decode


@pytest.mark.parametrize("fault", [_alter_tokens, _state_unchanged],
                         ids=["token-altered", "state-unchanged"])
def test_broken_timed_path_is_not_correct(cell, fault):
    out = tiny_run(cell, SEED, breaker=fault)
    assert not out["correct"]
    assert out["checks"]["logit_gap"]["value"] > \
        out["checks"]["logit_gap"]["limit"]


def test_control_fails_the_limit(cell):
    """The control, the reference from fp8 operands in the program's
    place: over a few hundred positions the tokens it puts first lie
    further below the reference's best than the cell's limit allows."""
    fam, d = cell.family, cell.family.dims(cell.conf)
    key = fam.seed_key(SEED)
    rng = np.random.default_rng(0)
    seqs = [rng.integers(0, d.V, n).astype(np.int32) for n in (700, 300)]
    rows = [np.arange(len(s) - 200, len(s)) for s in seqs]
    ref = fam.reference_logits(d, key, seqs, rows, row_len=1024)
    ctl = fam.reference_logits(d, key, seqs, rows, row_len=1024,
                               quant="fp8")
    limit = cell.limits["logit_gap"]
    assert not check.verdict(check.picked_gap(ref, ctl), limit, 0)
    assert check.verdict(check.picked_gap(ref, ref), limit, 0)
    assert not check.verdict(0.0, limit, 1)     # a failed request


def test_every_seed_serves_the_same_work(cell):
    """Same lengths in the same order for every seed, first wave too;
    the seed draws only the token ids."""
    mix, n = cell.mix, cell.conf["max_batch"]
    a = traffic.generate(mix, 1, n, 512, 1024)
    b = traffic.generate(mix, 2**40 + 3, n, 512, 1024)
    assert [len(p) for p in a.prompts] == [len(p) for p in b.prompts]
    assert a.outputs == b.outputs and a.first == b.first == n
    assert any(not np.array_equal(x, y) for x, y in zip(a.prompts,
                                                         b.prompts))
    c = traffic.generate(mix, 1, n, 512, 1024)
    assert all(np.array_equal(x, y) for x, y in zip(a.prompts, c.prompts))
    full = traffic.generate(mix, 1, 0, 512, 1024)
    for i in range(n):     # the first wave carries part of its output
        assert len(a.prompts[i]) + a.outputs[i] == \
            len(full.prompts[i]) + full.outputs[i]
        assert a.outputs[i] >= 1
    assert a.outputs[n:] == full.outputs[n:]
    lengths = sorted(zip(map(len, full.prompts[:traffic.QUANTILES]),
                         full.outputs[:traffic.QUANTILES]))
    assert lengths == sorted(map(tuple, traffic.quantile_pairs(mix, 1024)))
