"""A traced run of a tiny closed-loop cell on the CPU reads the per-layer
metrics that come from the engine's own step spans and counters:
``engine.host_ms``, ``engine.sample_ms``, ``engine.download_ms`` and
``engine.logits_mb``."""

import pytest

import bench_testlib as T
from harness import peaks, program

SEED = 2**31 + 21  # a seed past 32 signed bits
SPAN_METRICS = ("engine.host_ms", "engine.sample_ms", "engine.download_ms")


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # the CPU has no published peaks; the tiny cell borrows numbers so the
    # readers that need peaks run
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12, "int8_ops": 2e12,
                                        "hbm_bytes_per_s": 1e11})
        yield T.make_root(
            tmp_path_factory.mktemp("bench"),
            cells=[("tiny.closed", "tiny-qwen2", "tiny_closed")],
            configs={"tiny-qwen2": T.tiny_config("qwen2-1.5b", "tiny-qwen2")},
            mixes={"tiny_closed": T.tiny_mix("closed")},
        )


def test_traced_run_reads_the_engine_span_metrics(root):
    res = T.run_cell(root, "tiny.closed", SEED, 2.0, trace=True)
    assert res["correct"], res["compared"]
    m = res["metrics"]
    assert set(SPAN_METRICS) | {"engine.logits_mb"} <= set(m), m
    for name in SPAN_METRICS:
        assert m[name]["value"] > 0, name
    # every launch downloads each slot's f32 logits row, padded columns included
    conf = T.tiny_config("qwen2-1.5b", "tiny-qwen2")
    width = program.model_config(conf).padded_vocab
    slots = T.tiny_mix("closed")["engine"]["batch_slots"]
    assert m["engine.logits_mb"]["value"] == pytest.approx(slots * width * 4 / 1e6, rel=1e-12)
    assert m["engine.logits_mb"]["unit"] == "MB"


@pytest.mark.parametrize("name", SPAN_METRICS + ("engine.logits_mb",))
def test_a_program_without_the_counters_reads_nothing(name):
    """The parent of these metrics has no step phase counters: its traced
    runs leave the metrics out instead of failing."""
    from harness import cell
    from harness.context import Context

    stats0 = {"decode_steps": 0, "decode_tokens": 0, "prefill_tokens": 0}
    stats1 = {"decode_steps": 10, "decode_tokens": 40, "prefill_tokens": 0}
    ctx = Context(spec=None, arch=None, driver=None, device_kind="cpu", t0=0.0, t1=1.0,
                  stats0=stats0, stats1=stats1)
    assert cell.reader(T.REPO, "layer_metrics", name)(ctx) is None
