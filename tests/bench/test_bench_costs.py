"""Operations and bytes of one kernel call, by hand, with pad rows left out;
the dense GQA architecture's cost of one launch from the engine's record."""

from types import SimpleNamespace

import numpy as np
import pytest

import bench_testlib as T
from harness import context, costs

A = T.architecture("dense_gqa")


def test_one_dual_gemm_call():
    # 3 rows, K 256, one projection N 128, rank 128, groups of 128
    c = costs.dual_gemm(3, 256, [(128, 128, 128)], 128)
    assert c.ops == 2 * 3 * (256 * 128 + 256 * 128 + 128 * 128)
    codes = (256 * 128 + 256 * 128 + 128 * 128) / 2
    scales = 4 * (2 * 128 + 2 * 128 + 1 * 128)
    assert c.bytes == codes + scales + 2 * 3 * 256 + 2 * 3 * 128
    # a fused pair shares the activation rows: read once
    pair = costs.dual_gemm(3, 256, [(128, 128, 128), (256, 128, 128)], 128)
    one = costs.dual_gemm(3, 256, [(256, 128, 128)], 128)
    assert pair.ops == c.ops + one.ops
    assert pair.bytes == c.bytes + one.bytes - 2 * 3 * 256


def test_one_ragged_attention_call():
    # rows at positions 4 (decode, slot 0 with 4 cached rows) and 0, 1 (a
    # fresh chunk of slot 1): they attend 5, 1 and 2 keys
    c = costs.ragged_attention([5, 1, 2], [4, 0], n_heads=4, n_kv_heads=2, head_dim=128)
    assert c.ops == 4 * 8 * 4 * 128
    kv_row = 2 * 2 * 128 * 2
    assert c.bytes == 4 * kv_row + 3 * (2 * 4 * 128 * 2 + kv_row)
    assert c.ideal_s(1e12, 1e9) == pytest.approx(c.bytes / 1e9)


def test_launch_costs_leave_out_pad_rows():
    arch = A.Arch(n_layers=2, d_model=256, n_heads=2, n_kv_heads=1, head_dim=128,
                  d_ff=512, vocab=512, padded_vocab=512, qkv_bias=False, rope_theta=1e4,
                  rope_fraction=1.0, norm_eps=1e-5, rank=128, group=128, a_bits=4)

    class Spec:
        mix = {"engine": {"batch_slots": 4, "token_budget": 8}}

    b = 4
    # slot 2 decodes at position 6; slot 0 prefills positions 3..5; pad rows
    # carry the sentinel slot B
    slot = np.array([2, 0, 0, 0, b, b, b, b])
    pos = np.array([6, 3, 4, 5, 0, 0, 0, 0])
    ctx = np.array([3, 0, 6, 0])
    logit_idx = np.array([3, 0, 0, 0])  # slot 0 completes its prompt at row 3
    # the engine's records: a step before the window, one that launched
    # inside it and one inside it that launched nothing
    records = [SimpleNamespace(t0=-1.0, t1=-0.5, slot=slot, pos=pos, ctx=ctx, logit_idx=logit_idx),
               SimpleNamespace(t0=0.2, t1=0.4, slot=slot, pos=pos, ctx=ctx, logit_idx=logit_idx),
               SimpleNamespace(t0=0.5, t1=0.6, slot=None, pos=None, ctx=None, logit_idx=None)]
    driver = SimpleNamespace(engine=SimpleNamespace(step_records=records))
    padded = context.Context(spec=Spec, arch=arch, driver=driver, device_kind="",
                             t0=0, t1=1, stats0={"decode_steps": 7},
                             stats1={"decode_steps": 8}, architecture=A)
    (c,) = padded.launch_costs()
    per_layer = (costs.dual_gemm(4, 256, [(256, 128, 128), (128, 128, 128), (128, 128, 128)], 128)
                 + costs.dual_gemm(4, 256, [(256, 128, 128)], 128)
                 + costs.dual_gemm(4, 256, [(512, 128, 128), (512, 128, 128)], 128)
                 + costs.dual_gemm(4, 512, [(256, 128, 128)], 128))
    assert c["linear"].ops == 2 * per_layer.ops
    assert c["linear"].bytes == 2 * per_layer.bytes
    att = costs.ragged_attention([7, 4, 5, 6], [3, 6], 2, 1, 128)
    assert c["attention"].ops == 2 * att.ops
    assert c["head"] == costs.lm_head(2, 256, 512)  # slot 2's decode row, slot 0's last row
    assert c == A.launch_cost(arch, records[1], b)


def test_launches_lost_from_the_records_are_an_error():
    """A window with more launches than ``engine.step_records`` still holds
    would cost fewer launches than ran: it raises instead."""
    rec = SimpleNamespace(t0=0.2, t1=0.4, slot=np.array([0]), pos=np.array([0]),
                          ctx=np.array([0]), logit_idx=np.array([0]))
    driver = SimpleNamespace(engine=SimpleNamespace(step_records=[rec]))
    ctx = context.Context(spec=None, arch=None, driver=driver, device_kind="", t0=0, t1=1,
                          stats0={"decode_steps": 0}, stats1={"decode_steps": 2})
    with pytest.raises(RuntimeError, match="1 of the window's 2 launches"):
        ctx.launches()


def test_pool_pages_hold_every_slot_timeline():
    import json

    from harness import cell, program

    conf = json.loads((T.BENCH / "configs" / "phi4-mini-3.8b.json").read_text())
    arch = A.arch_of(program.model_config(conf))
    # 32 layers x 8 KV heads x 128 x K and V x bf16: 128 KiB a token
    assert A.kv_bytes_per_token(arch) == 128 * 1024
    eng = {"batch_slots": 16, "max_len": 512, "page_size": 16}
    assert cell.pool_pages(A.kv_bytes_per_token(arch), eng) == (16 * 32, 16 * 128 * 1024)
