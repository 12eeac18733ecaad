"""A whole run off the chip: the sound program passes the check and its
control does not, and with the timed path broken underneath the check
comes out false for every fault a served cell can have."""
import jax.numpy as jnp
import pytest

from repro.core import paged_runner
from bench_tiny import LIMITS, run


def test_sound_run_passes_and_its_control_does_not():
    res = run(control=True)
    assert res["correct"], res["checks"]
    assert res["readings"]["checked_tokens"] >= LIMITS["checked_tokens"]
    # the control, the reference in fp8 put in the program's place, is
    # judged by the harness's own verdict with the cell's limits
    fp8 = res["controls"]["fp8"]
    assert not fp8["correct"], fp8["checks"]
    assert fp8["checks"]["mean_gap"]["value"] > LIMITS["mean_gap"]


def _altered_token(monkeypatch):
    real = paged_runner.batched_sample

    def sample(logits, *a, **k):
        out = real(logits, *a, **k)
        return ((out[0] + 1) % logits.shape[-1],) + tuple(out[1:])
    monkeypatch.setattr(paged_runner, "batched_sample", sample)


def _state_unchanged(monkeypatch):
    # the step writes no K/V: every later step reads the pools unchanged
    def scatter(self, pools, li, page_idx, page_off, k, v):
        return pools[0][li], pools[1][li], None, None
    monkeypatch.setattr(paged_runner.PagedModelRunner, "_scatter_kv",
                        scatter)


def _half_batch_left_out(monkeypatch):
    real = paged_runner.PagedModelRunner._ragged_logits

    def logits(self, params, kp, vp, ks, vs, tokens, pos, page_tables,
               contexts, *rest):
        out = real(self, params, kp, vp, ks, vs, tokens, pos, page_tables,
                   contexts, *rest)
        live = jnp.sum(contexts > 0)
        back = jnp.arange(contexts.shape[0]) >= (live + 1) // 2
        return (jnp.where(back[:, None, None], 0.0, out[0]),) + out[1:]
    monkeypatch.setattr(paged_runner.PagedModelRunner, "_ragged_logits",
                        logits)


@pytest.mark.parametrize("fault", [_altered_token, _state_unchanged,
                                   _half_batch_left_out])
def test_fault_is_caught(fault, monkeypatch):
    fault(monkeypatch)
    res = run()
    assert not res["correct"], res["checks"]
