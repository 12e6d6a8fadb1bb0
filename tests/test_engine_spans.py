"""The serving engine's step spans and counters (docs/serving.md
"Observability"): the phases of a ragged step partition it, the logits and
jit-miss counters count what happened, the step records hold the rows each
launch uploaded, the spans land in the profiler's trace on the same clock as
the records, and the bucketed and speculative sync points are spanned too."""

import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ModelConfig
from repro.launch.serve import _PHASES, ContinuousBatchingEngine, Request
from repro.models import dense

jax.config.update("jax_platform_name", "cpu")

# vocab 200 pads to 256 columns: the logits counter sees the padded width
CFG = ModelConfig(
    name="tiny-spans", family="dense", n_layers=2, d_model=64, n_heads=4,
    n_kv_heads=2, head_dim=16, d_ff=128, vocab=200, remat=False,
)
B = 4


@pytest.fixture(scope="module")
def params():
    return dense.init_params(CFG, jax.random.PRNGKey(0))


def _requests(n, seed=0, max_new=(8, 20)):
    rng = np.random.default_rng(seed)
    return [Request(rng.integers(1, 200, size=int(rng.integers(4, 40))).tolist(),
                    max_new=int(rng.integers(*max_new)))
            for _ in range(n)]


def _ragged(params, **kw):
    return ContinuousBatchingEngine(CFG, params, batch_slots=B, max_len=96, paged=True,
                                    ragged=True, token_budget=16, **kw)


def _launches(eng):
    return [r for r in eng.step_records if r.rows]


def test_phases_partition_the_ragged_step(params):
    eng = _ragged(params)
    for r in _requests(16):
        eng.submit(r)
    for _ in range(50):
        eng.step()
    st = eng.stats
    assert st["step_n"] == 50 and st["decode_steps"] >= 40
    phases = sum(st[f"{p}_s"] for p in _PHASES)
    assert phases == pytest.approx(st["step_s"], rel=0.01)
    assert st["upload_n"] == 0  # only the speculative rewind uploads
    for p in ("sample", "schedule", "launch", "device_wait", "download", "commit"):
        assert st[f"{p}_n"] >= st["decode_steps"], p
    # each record: the same partition, phases in step order
    for rec in eng.step_records:
        assert sum(rec.phases.values()) == pytest.approx(rec.t1 - rec.t0, rel=0.01, abs=2e-5)
        assert rec.spans[0][1] == rec.t0
        assert all(a[2] == b[1] for a, b in zip(rec.spans, rec.spans[1:]))
    names = [n for n, *_ in _launches(eng)[-1].spans]
    assert names == ["admit", "sample", "schedule", "launch", "device_wait", "download",
                     "commit", "admit"]


def test_logits_bytes_and_jit_misses(params):
    eng = _ragged(params)
    for r in _requests(6, seed=1):
        eng.submit(r)
    eng.step()
    assert eng.stats["decode_steps"] == 1
    assert eng.stats["jit_misses"] == 1 and eng.step_records[-1].jit_miss
    while eng.queue or any(eng.slots):
        eng.step()
    launches = eng.stats["decode_steps"]
    assert launches > 10
    assert eng.stats["jit_misses"] == 1
    assert eng.compile_stats()["ragged_traces"] == 1
    assert not any(r.jit_miss for r in list(eng.step_records)[1:])
    assert eng.stats["logits_bytes"] == launches * B * CFG.padded_vocab * 4
    assert all(r.logits_bytes == B * CFG.padded_vocab * 4 for r in _launches(eng))


def test_ragged_traces_sees_a_recompile(params):
    """A second executable for the same token budget (here the weights change
    dtype) is a recompile: the jit-miss counters see it."""
    eng = _ragged(params)
    for r in _requests(4, seed=2):
        eng.submit(r)
    eng.step()
    eng.params = jax.tree.map(lambda x: x.astype(jnp.float32), eng.params)
    eng.step()
    assert eng.compile_stats()["ragged_traces"] == 2
    assert eng.stats["jit_misses"] == 2 and eng.step_records[-1].jit_miss


def test_step_records_hold_the_launched_rows(params):
    eng = _ragged(params)
    seen = []
    step = eng._ragged

    def log(params, state, tokens, slot, pos, ctx, logit_idx):
        seen.append(tuple(np.asarray(a) for a in (slot, pos, ctx, logit_idx)))
        return step(params, state, tokens, slot, pos, ctx, logit_idx)

    eng._ragged = log
    for r in _requests(8, seed=3):
        eng.submit(r)
    eng.run_until_done()
    recs = _launches(eng)
    assert len(recs) == len(seen) == eng.stats["decode_steps"]
    for rec, (slot, pos, ctx, logit_idx) in zip(recs, seen):
        np.testing.assert_array_equal(rec.slot, slot)
        np.testing.assert_array_equal(rec.pos, pos)
        np.testing.assert_array_equal(rec.ctx, ctx)
        np.testing.assert_array_equal(rec.logit_idx, logit_idx)
        assert rec.rows == int((slot < B).sum())


def test_spans_share_the_trace_clock(params, tmp_path):
    """Every phase of the traced steps is a host event of the profiler's
    trace, in the records' order, and one offset between the trace's clock
    and the records' puts each event inside its recorded span to 50 us. An
    event opens after its span's recorded start and closes before its
    recorded end, so each bounds the offset from one side; the bounds must
    meet however long the host paused in between."""
    from jax.profiler import ProfileData

    eng = _ragged(params)
    for r in _requests(8, seed=4):
        eng.submit(r)
    for _ in range(3):
        eng.step()  # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    for _ in range(3):
        eng.step()
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = sorted(
        ((ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
         for plane in ProfileData.from_file(path).planes if plane.name.startswith("/host:")
         for line in plane.lines for ev in line.events
         if ev.name.startswith("engine.") and ev.name != "engine.step"),
        key=lambda e: e[1],
    )
    spans = [s for rec in list(eng.step_records)[-3:] for s in rec.spans]
    assert [name for name, *_ in events] == [f"engine.{name}" for name, *_ in spans]
    assert {name for name, *_ in spans} == {"admit", "sample", "schedule", "launch",
                                           "device_wait", "download", "commit"}
    above = max(end - t1 for (_, _, end), (_, _, t1) in zip(events, spans))
    below = min(start - t0 for (_, start, _), (_, t0, _) in zip(events, spans))
    assert above <= below + 50e-6


@pytest.mark.parametrize("kw, rewind", [({}, False), ({"paged": True}, False),
                                        ({"paged": True, "speculation": True, "spec_k": 2},
                                         True)])
def test_bucketed_and_speculative_sync_points_are_spanned(params, kw, rewind):
    eng = ContinuousBatchingEngine(CFG, params, batch_slots=2, max_len=64, **kw)
    for r in _requests(3, seed=5, max_new=(4, 8)):
        eng.submit(r)
    eng.run_until_done()
    st = eng.stats
    assert st["decode_steps"] > 0
    # each decode reads pos and downloads logits; each prefill downloads too
    assert st["download_n"] == st["device_wait_n"] > 2 * st["decode_steps"]
    assert (st["upload_n"] > 0) == rewind
    assert st["logits_bytes"] > 0 and st["jit_misses"] >= 2  # prefill and decode
    launched = [r for r in eng.step_records if "launch" in r.phases]
    assert launched
    for rec in launched:
        assert {"device_wait", "download"} <= set(rec.phases) and rec.logits_bytes > 0
        assert ("upload" in rec.phases) == rewind
