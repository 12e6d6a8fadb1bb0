"""Whole runs of tiny cells on the CPU, past the harness's look for a chip:
the metrics come out, a cell, a mix and a per-layer metric are added with
new files alone, and a broken timed path makes ``correct`` false."""

import pytest

import bench_testlib as T
from harness import peaks

SEED = 2**31 + 11  # past 32 signed bits, like the driver's seeds

NEW_METRIC = '''"""Launched steps per second of the window (a test metric)."""


def read(ctx):
    steps = [s for s in ctx.steps() if s.launched]
    return None if not steps else 100.0 * len(steps) / (ctx.t1 - ctx.t0)
'''


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # the CPU has no published peaks; the tiny cells borrow numbers so the
    # readers that need peaks run
    peaks.PEAKS.setdefault("cpu", {"bf16_flops": 1e12, "int8_ops": 2e12,
                                   "hbm_bytes_per_s": 1e11})
    return T.make_root(
        tmp_path_factory.mktemp("bench"),
        cells=[("tiny.closed", "tiny-qwen2", "tiny_closed"),
               ("tiny.sessions", "tiny-phi", "tiny_sessions")],
        configs={"tiny-qwen2": T.tiny_config("qwen2-1.5b", "tiny-qwen2"),
                 "tiny-phi": T.tiny_config("phi4-mini-3.8b", "tiny-phi")},
        mixes={"tiny_closed": T.tiny_mix("closed"), "tiny_sessions": T.tiny_mix("sessions")},
        metrics={"engine.launch_rate": NEW_METRIC},
    )


@pytest.mark.parametrize("workload", ["tiny.closed", "tiny.sessions"])
def test_end_to_end_metrics(root, workload):
    res = T.run_cell(root, workload, SEED, 2.0, trace=False)
    assert res["correct"], res["compared"]
    m = res["metrics"]
    assert {"setup_s", "itl_p95_ms", "ttft_p90_ms", "output_tok_s"} <= set(m)
    assert all(v["value"] > 0 for v in m.values())
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "compared"
    assert res["compared"]["window_compiles"]["value"] == 0
    assert res["compared"]["ref_routes"]["value"] == 0


@pytest.mark.parametrize("workload", ["tiny.closed", "tiny.sessions"])
def test_traced_run_reads_the_per_layer_metrics(root, workload):
    res = T.run_cell(root, workload, SEED + 1, 2.0, trace=True)
    assert res["correct"], res["compared"]
    m = res["metrics"]
    # the CPU runs no device operations, so the trace readers return nothing
    want = {"engine.step_ms", "engine.pad_row_share", "step.mfu", "engine.launch_rate"}
    if workload == "tiny.sessions":
        want.add("prefix.hit_share")
    assert want <= set(m), m
    assert 0 <= m["engine.pad_row_share"]["value"] < 100
    assert 0 < m["step.mfu"]["value"] < 100
    assert "dual_gemm_roofline" not in m and "device.idle_share" not in m
    assert set(res["breakdown"]) == {"device_ops", "idle_gaps"}
    assert {"busy_s", "window_s"} <= set(res["device"])
    if workload == "tiny.sessions":
        assert m["prefix.hit_share"]["value"] > 10  # system prompts and history


def alter_tokens(engine):
    """A token altered where it is produced: every sampled token is moved
    to its neighbour in the vocabulary."""
    sample = engine._sample
    engine._sample = lambda req: (sample(req) + 1) % engine.cfg.vocab


def keep_state(engine):
    """A step that returns its state unchanged: the launch computes, but its
    cache writes are dropped."""
    step = engine._ragged

    def frozen(params, state, *args):
        logits, _ = step(params, state, *args)
        return logits, state

    engine._ragged = frozen


@pytest.mark.parametrize("fault", [alter_tokens, keep_state])
def test_a_broken_timed_path_is_not_correct(root, fault):
    res = T.run_cell(root, "tiny.closed", SEED, 2.0, trace=False, faults=fault)
    assert not res["correct"]
    gap = res["compared"]["logit_gap_trim90"]
    assert gap["value"] > gap["limit"]
