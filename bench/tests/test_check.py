"""The check that decides ``correct``, at a size a CPU holds: sound runs
pass it, and each way the served path can go wrong fails it.

The cell is ``bench/tests/tiny.py``'s; the harness's chip check is skipped
(``run.run`` is given the device), everything after it runs as on the chip.
Its limit on the mean logit gap (0.004) sits between sound tiny runs
(0.0009-0.0018 on three seeds) and the float8 control (0.0081-0.0096), as
the cells' limits do at theirs.
"""
import jax
import jax.numpy as jnp
import pytest

from bench import control, run
from bench.tests import tiny

SEED = 2**31 + 4242


def _jit(engine):
    return jax.jit(engine._paged_decode_step)  # no donation: the faults reuse the pool


def stale_state(engine):
    """The decode step returns the KV pool unchanged."""
    f = _jit(engine)
    engine._paged_decode_jit = lambda p, t, c, pg, cl: (f(p, t, c, pg, cl)[0], c)


def half_batch(engine):
    """Only the first half of the slots is computed; the rest get its logits."""
    f = _jit(engine)

    def step(p, t, c, pg, cl):
        b = t.shape[0]
        keep = jnp.arange(b) < b // 2
        logits, c = f(p, jnp.where(keep, t, 0), c, jnp.where(keep[:, None], pg, 0),
                      jnp.where(keep, cl, 0))
        return jnp.where(keep[:, None], logits, logits[:1]), c

    engine._paged_decode_jit = step


def altered_token(engine):
    """Slot 0's token comes out one id past the one the logits chose."""
    f = _jit(engine)

    def step(p, t, c, pg, cl):
        logits, c = f(p, t, c, pg, cl)
        best = jnp.argmax(logits[0])
        nxt = (best + 1) % logits.shape[1]
        return logits.at[0, nxt].set(logits[0, best] + 1.0), c

    engine._paged_decode_jit = step


@pytest.mark.parametrize("name", ["tiny.chat", "tiny.code"])
def test_sound_runs_are_correct(name):
    r = run.run(tiny.cell(name), seed=SEED, seconds=2.0, trace=False, device=tiny.DEVICE)
    assert r["correct"], r["checks"]
    assert r["window_compiles"] == 0
    assert list(r)[-1] == "checks"
    pool = r["pool"]
    assert 0 <= pool["filled_by_live"] <= pool["reserved_by_live"] <= pool["pages"]
    assert max(pool["filled_by_live"], 1) <= pool["in_use"] <= pool["pages"]
    assert set(r["metrics"]) == ({"setup_s", "tpot_p95_ms", "ttft_p95_ms"} if name == "tiny.code"
                                 else {"setup_s", "tpot_p95_ms"})


@pytest.mark.parametrize("fault", [stale_state, half_batch, altered_token])
def test_a_broken_served_path_is_not_correct(fault):
    r = run.run(tiny.cell(), seed=SEED, seconds=2.0, trace=False, device=tiny.DEVICE,
                engine_hook=fault)
    assert not r["correct"], r["checks"]


def test_the_float8_control_fails_the_limit():
    cell = tiny.cell()
    got = control.readings(cell, SEED + 1, 2.0, tiny.DEVICE)
    assert got["program_correct"] and not got["control_correct"], got
    assert got["program"] <= cell.params["mean_logit_gap"] < got["control"], got
