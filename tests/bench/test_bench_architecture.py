"""A configuration names its architecture (``bench/architectures/<name>.py``),
and a new architecture is new files only: a checkout that adds an untied-head
variant of the dense decoder, its reference, a configuration and a mix, and
changes no file it had, runs a tiny cell of it on the CPU ``correct``, with
every per-layer metric that the dense cell reads. Launch costs come from the
engine's own step records, as the harness's wrapper of the jitted step
(``LaunchLog``, which they replace) gave them."""

import time

import numpy as np
import pytest

import bench_testlib as T
from harness import cell, context, costs, peaks

SEED = 2**31 + 31  # past 32 signed bits, as a run's seed may be
UNTIED = T.REPO / "tests" / "bench" / "untied"
NEW_FILES = {
    "architectures/dense_gqa_untied.py": UNTIED / "architectures" / "dense_gqa_untied.py",
    "references/dense_gqa_untied.py": UNTIED / "references" / "dense_gqa_untied.py",
}


def untied_config() -> dict:
    # the tiny qwen2 limit holds here too: CPU readings of logit_gap_trim90
    # over seeds 1-6, 2**31 + 11 and 2**31 + 12: program 0.016-0.025,
    # control 0.118-0.181 (limit 0.05)
    conf = T.tiny_config("qwen2-1.5b", "tiny-untied")
    conf["architecture"] = conf["reference"] = "dense_gqa_untied"
    conf["overrides"]["tie_embeddings"] = False
    return conf


def noarch_config() -> dict:
    conf = T.tiny_config("qwen2-1.5b", "tiny-noarch")
    del conf["architecture"]
    return conf


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    # the CPU has no published peaks; the tiny cells borrow numbers so the
    # readers that need peaks run
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(peaks.PEAKS, "cpu", {"bf16_flops": 1e12, "int8_ops": 2e12,
                                        "hbm_bytes_per_s": 1e11})
        yield T.make_root(
            tmp_path_factory.mktemp("arch"),
            cells=[("tiny.dense", "tiny-qwen2", "tiny_closed"),
                   ("tiny.untied", "tiny-untied", "tiny_untied"),
                   ("tiny.noarch", "tiny-noarch", "tiny_closed")],
            configs={"tiny-qwen2": T.tiny_config("qwen2-1.5b", "tiny-qwen2"),
                     "tiny-untied": untied_config(), "tiny-noarch": noarch_config()},
            mixes={"tiny_closed": T.tiny_mix("closed"), "tiny_untied": T.tiny_mix("closed")},
            files=NEW_FILES,
        )


def test_the_checkout_changes_no_file_it_had(root):
    for f in T.BENCH.rglob("*"):
        if f.is_file() and "__pycache__" not in f.parts:
            assert (root / "bench" / f.relative_to(T.BENCH)).read_bytes() == f.read_bytes(), f


def test_a_new_architecture_runs_correct_with_every_metric(root):
    dense = T.run_cell(root, "tiny.dense", SEED, 2.0, trace=True)
    untied = T.run_cell(root, "tiny.untied", SEED, 2.0, trace=True)
    for res in (dense, untied):
        assert res["correct"], res["compared"]
        assert res["compared"]["logit_gap_trim90"]["value"] <= \
            res["compared"]["logit_gap_trim90"]["limit"]
    assert set(untied["metrics"]) == set(dense["metrics"]), (untied["metrics"], dense["metrics"])
    assert {"step.mfu", "engine.step_ms", "engine.pad_row_share"} <= set(untied["metrics"])
    plain = T.run_cell(root, "tiny.untied", SEED + 1, 2.0, trace=False)
    assert plain["correct"], plain["compared"]
    assert {"setup_s", "itl_p95_ms", "output_tok_s"} <= set(plain["metrics"])


def test_the_untied_head_is_what_the_program_serves(root):
    """Against the tied reference the untied cell's served tokens are far
    off: the program serves the head the new architecture draws."""
    spec = cell.load(root, "tiny.untied")
    spec.conf["reference"] = "dense_gqa"
    import jax

    res = cell.run(spec, SEED + 2, 2.0, False, jax.devices()[0], t_start=time.monotonic())
    gap = res["compared"]["logit_gap_trim90"]
    assert not res["correct"] and gap["value"] > gap["limit"], gap


def test_a_configuration_without_architecture_is_refused(root):
    with pytest.raises(ValueError, match='"architecture"'):
        cell.load(root, "tiny.noarch")


class LaunchLog:
    """The harness's former record of launches: it wrapped the engine's
    jitted ragged step and kept each call's row metadata, stamped when the
    call returned."""

    def __init__(self, engine):
        self.fn = engine._ragged
        self.records: list = []
        engine._ragged = self

    def __call__(self, params, state, tokens, slot, pos, ctx, logit_idx):
        out = self.fn(params, state, tokens, slot, pos, ctx, logit_idx)
        self.records.append((time.monotonic(), slot, pos, ctx, logit_idx))
        return out


def launchlog_costs(arch, launches: list, b: int) -> list:
    """The launch costs as the harness computed them from ``LaunchLog``."""
    a = arch
    lin = a.linears()
    out = []
    for _, slot, pos, ctx, logit_idx in launches:
        real = slot < b
        m = int(real.sum())
        seg = {name: (n, a.rank_of(k, n), min(a.group, a.rank_of(k, n)))
               for name, (_, k, n, _) in lin.items()}
        d, f = a.d_model, a.d_ff
        layer = (costs.dual_gemm(m, d, [seg["q"], seg["k"], seg["v"]], a.group)
                 + costs.dual_gemm(m, a.n_heads * a.head_dim, [seg["o"]], a.group)
                 + costs.dual_gemm(m, d, [seg["gate"], seg["up"]], a.group)
                 + costs.dual_gemm(m, f, [seg["down"]], a.group))
        slots = sorted(set(int(s) for s in slot[real]))
        att = costs.ragged_attention((pos[real] + 1).tolist(), [int(ctx[s]) for s in slots],
                                     a.n_heads, a.n_kv_heads, a.head_dim)
        used = sum(1 for s in slots if int(slot[int(logit_idx[s])]) == s)
        out.append({"linear": costs.Cost(layer.ops * a.n_layers, layer.bytes * a.n_layers),
                    "attention": costs.Cost(att.ops * a.n_layers, att.bytes * a.n_layers),
                    "head": costs.lm_head(used, d, a.vocab)})
    return out


def test_launch_costs_from_step_records_match_launchlog(root, monkeypatch):
    logs, seen = [], []

    def install(engine):
        logs.append(LaunchLog(engine))

    def spy(self):
        out = orig(self)
        seen.append((self, out))
        return out

    orig = context.Context.launch_costs
    monkeypatch.setattr(context.Context, "launch_costs", spy)
    res = T.run_cell(root, "tiny.dense", SEED + 3, 2.0, trace=True, faults=install)
    assert res["correct"], res["compared"]
    ctx, got = seen[0]
    assert all(out == got for _, out in seen)
    b = ctx.engine_conf["batch_slots"]
    logged = [(t, *(np.asarray(a) for a in arrays)) for t, *arrays in logs[0].records
              if ctx.t0 <= t <= ctx.t1]
    assert len(got) == len(logged) == ctx.delta("decode_steps") > 0
    assert got == launchlog_costs(ctx.arch, logged, b)
