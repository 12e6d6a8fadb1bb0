"""Persisted (block_m, block_n, block_k) autotuner for the quantized GEMMs.

Replaces the old ``ops.pick_blocks`` heuristic, which had two fallback bugs:
``bn = n`` for non-128-multiple N (a 13k-wide single block blows VMEM) and
``bk = max(bk, group)`` which can violate ``k % block_k == 0`` and trip the
kernel's tiling assert. Here the contract is explicit:

* :func:`heuristic_blocks` is the **deterministic fallback**: it returns a
  validated, MXU-aligned block triple, or ``None`` when the shape is not
  tileable at all — the dispatch layer routes ``None`` to the jnp oracle
  instead of asserting.
* :func:`autotune_blocks` is the **measured sweep**: it times every
  candidate triple for a (kind, M-regime, N, K, group, rank) key and
  persists the winner to ``artifacts/tune/<kind>.json`` via
  :class:`TuneCache`. Keys use the M *regime* (decode vs prefill), not the
  exact M, so one serving deployment warms the cache for every batch size
  in its regime.
* :func:`get_blocks` is what the dispatch layer calls on the hot path:
  cache hit -> tuned blocks; miss -> heuristic. Never measures implicitly.

Kinds: ``dual_prefill`` / ``dual_decode`` / ``w4a16`` for single packs, plus
``dual_prefill_fused`` / ``dual_decode_fused`` for horizontally fused
projection groups (q/k/v, gate/up). The fused kinds use the same schedules;
the dispatch layer passes ``n = gcd(segment widths)`` so every candidate
``block_n`` tiles every segment (N blocks never straddle a segment
boundary), and ``rank = sum(segment ranks)`` (the stacked-U rank axis).

Cache file format (schema 1)::

    {
      "schema": 1,
      "backend": "tpu",
      "entries": {
        "dual/prefill/n4096/k14336/g128/r128": {
          "blocks": [128, 256, 512],
          "best_us": 812.4,
          "candidates": 9
        }
      }
    }

The cache directory is ``artifacts/tune`` (override: ``REPRO_TUNE_DIR``).
"""

from __future__ import annotations

import json
import os
import time
import warnings
from pathlib import Path
from typing import Callable, Optional

import jax

from repro.kernels.contracts import LANE, SUBLANE

__all__ = [
    "TuneCache",
    "autotune_blocks",
    "candidate_blocks",
    "cache_key",
    "default_cache",
    "get_blocks",
    "heuristic_blocks",
    "regime",
]

SCHEMA = 1

# Decode regime bound — kept in sync with twinquant_dual_gemv.DECODE_M_MAX
# (imported there from here to keep this module kernel-import-free).
DECODE_M_MAX = 8

_BN_CANDIDATES = (512, 256, 128)
_BK_CANDIDATES = (1024, 512, 256, 128)
_DECODE_KINDS = ("dual_decode", "dual_decode_fused")


def regime(m: int) -> str:
    """Shape regime of an M (flattened token-row count)."""
    return "decode" if m <= DECODE_M_MAX else "prefill"


def cache_key(kind: str, m: int, n: int, k: int, group: int, rank: int = 0) -> str:
    """Deterministic cache key: M enters only through its regime."""
    return f"{kind}/{regime(m)}/n{n}/k{k}/g{group}/r{rank}"


def _round_up_pow2(x: int) -> int:
    p = 8
    while p < x and p < 128:
        p *= 2
    return p


def heuristic_blocks(
    kind: str, m: int, n: int, k: int, group: int, rank: int = 0
) -> Optional[tuple[int, int, int]]:
    """Deterministic block triple for a tileable shape, else ``None``.

    Validity contract (matches ``kernels/contracts.py``, so every triple
    returned here is one the TPU compiler accepts):
      * ``k % block_k == 0`` and ``block_k % group == 0``, with ``block_k``
        a multiple of 128 or the whole K (the (8, 128) block rule)
      * ``n % block_n == 0`` with ``block_n`` lane aligned (128x)
      * dual kernels slice each scale group at a dynamic lane offset, so
        their ``group`` must be a multiple of 128 (the lane-aligned store
        rule); ``rank % rgroup == 0`` is checked by the dispatch layer.
    """
    if k <= 0 or n <= 0 or m <= 0:
        return None
    if k % group != 0 or group % 2 != 0:
        return None
    if kind.startswith("dual") and group % LANE != 0:
        return None
    bn = next((c for c in _BN_CANDIDATES if n % c == 0), None)
    if bn is None:
        return None
    if kind in _DECODE_KINDS:
        # whole-K schedule: block_k is unused by the gemv grid but recorded
        # as K so cache entries stay self-describing. For the fused kind the
        # caller passes n = gcd over segment widths, so bn | every segment.
        return (DECODE_M_MAX, bn, k)
    bm = min(128, _round_up_pow2(m))
    return (bm, bn, _block_ks(k, group)[0])


def _block_ks(k: int, group: int) -> list[int]:
    """Legal K blocks, largest first; the whole K when no candidate tiles
    it (a full-extent block always satisfies the (8, 128) rule)."""
    return [c for c in _BK_CANDIDATES if k % c == 0 and c % group == 0] or [k]


def candidate_blocks(
    kind: str, m: int, n: int, k: int, group: int, rank: int = 0
) -> list[tuple[int, int, int]]:
    """All valid block triples for the measured sweep (deterministic order)."""
    base = heuristic_blocks(kind, m, n, k, group, rank)
    if base is None:
        return []
    if kind in _DECODE_KINDS:
        return [(DECODE_M_MAX, bn, k) for bn in _BN_CANDIDATES if n % bn == 0]
    bms = sorted({min(128, _round_up_pow2(m)), 128} | ({64} if m >= 64 else set()))
    bns = [c for c in _BN_CANDIDATES if n % c == 0]
    return [(bm, bn, bk) for bm in bms for bn in bns for bk in _block_ks(k, group)]


class TuneCache:
    """One JSON file per kernel kind under the tune directory."""

    def __init__(self, directory: Optional[os.PathLike] = None):
        if directory is None:
            directory = os.environ.get("REPRO_TUNE_DIR", "artifacts/tune")
        self.dir = Path(directory)
        self._loaded: dict[str, dict] = {}

    def _path(self, kind: str) -> Path:
        return self.dir / f"{kind}.json"

    def _load(self, kind: str) -> dict:
        if kind not in self._loaded:
            p = self._path(kind)
            if p.exists():
                try:
                    doc = json.loads(p.read_text())
                except (OSError, json.JSONDecodeError) as e:
                    warnings.warn(
                        f"ignoring unreadable tune cache {p}: {e} "
                        "(falling back to heuristic blocks)",
                        stacklevel=3,
                    )
                    doc = {}
                if not isinstance(doc, dict):
                    warnings.warn(
                        f"ignoring tune cache {p}: expected a JSON object, "
                        f"got {type(doc).__name__} (falling back to heuristic blocks)",
                        stacklevel=3,
                    )
                    doc = {}
                elif doc and doc.get("schema") != SCHEMA:
                    warnings.warn(
                        f"ignoring tune cache {p}: schema "
                        f"{doc.get('schema')!r} != {SCHEMA} "
                        "(falling back to heuristic blocks)",
                        stacklevel=3,
                    )
                    doc = {}
            else:
                doc = {}
            doc.setdefault("schema", SCHEMA)
            doc.setdefault("backend", jax.default_backend())
            doc.setdefault("entries", {})
            self._loaded[kind] = doc
        return self._loaded[kind]

    def lookup(self, key: str) -> Optional[tuple[int, int, int]]:
        kind = key.split("/", 1)[0]
        entries = self._load(kind).get("entries")
        entry = entries.get(key) if isinstance(entries, dict) else None
        if not isinstance(entry, dict):
            return None
        blocks = entry.get("blocks")
        if not (isinstance(blocks, list) and len(blocks) == 3):
            return None
        try:
            return tuple(int(b) for b in blocks)
        except (TypeError, ValueError):
            # garbage values inside a well-shaped entry: treat as a miss (the
            # dispatch layer degrades to heuristic blocks, never crashes)
            warnings.warn(
                f"ignoring malformed tune-cache entry {key!r}: "
                f"blocks={blocks!r}",
                stacklevel=2,
            )
            return None

    def store(self, key: str, blocks: tuple[int, int, int], **meta) -> None:
        kind = key.split("/", 1)[0]
        doc = self._load(kind)
        doc["entries"][key] = {"blocks": [int(b) for b in blocks], **meta}
        self.dir.mkdir(parents=True, exist_ok=True)
        self._path(kind).write_text(json.dumps(doc, indent=2, sort_keys=True) + "\n")

    def clear(self) -> None:
        self._loaded = {}


_default_cache: Optional[TuneCache] = None


def default_cache() -> TuneCache:
    global _default_cache
    if _default_cache is None:
        _default_cache = TuneCache()
    return _default_cache


def blocks_valid(
    kind: str, blocks: tuple[int, int, int], n: int, k: int, group: int
) -> bool:
    """Do these blocks satisfy the kernel tiling contract for (n, k, group),
    the TPU's (8, 128) block rule included?"""
    bm, bn, bk = blocks
    if bm <= 0 or bn <= 0 or bk <= 0 or n % bn != 0 or bn % LANE != 0:
        return False
    if kind.startswith("dual") and group % LANE != 0:
        return False
    if kind in _DECODE_KINDS:
        return k % group == 0
    return (
        bm % SUBLANE == 0 and k % bk == 0 and bk % group == 0
        and (bk % LANE == 0 or bk == k)
    )


def get_blocks(
    kind: str,
    m: int,
    n: int,
    k: int,
    group: int,
    rank: int = 0,
    cache: Optional[TuneCache] = None,
) -> Optional[tuple[int, int, int]]:
    """Hot-path lookup: tuned blocks if persisted, else the heuristic.

    Cache hits are re-validated against the kernel tiling contract — a
    stale or foreign entry (tuned before a kernel change, hand-edited,
    copied from another deployment) must degrade to the heuristic, never
    resurrect the tiling asserts the dispatch layer exists to remove."""
    cache = cache or default_cache()
    hit = cache.lookup(cache_key(kind, m, n, k, group, rank))
    if hit is not None and blocks_valid(kind, hit, n, k, group):
        return hit
    return heuristic_blocks(kind, m, n, k, group, rank)


def _measure(call: Callable[[], jax.Array], iters: int = 5, warmup: int = 2) -> float:
    """Median wall-clock seconds per call (block_until_ready)."""
    for _ in range(warmup):
        jax.block_until_ready(call())
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(call())
        times.append(time.perf_counter() - t0)
    times.sort()
    return times[len(times) // 2]


def autotune_blocks(
    kind: str,
    make_call: Callable[[tuple[int, int, int]], Callable[[], jax.Array]],
    m: int,
    n: int,
    k: int,
    group: int,
    rank: int = 0,
    cache: Optional[TuneCache] = None,
    iters: int = 5,
) -> Optional[tuple[int, int, int]]:
    """Measured sweep over candidate blocks; persists and returns the winner.

    ``make_call(blocks)`` must return a zero-arg callable running the kernel
    at those blocks (the autotuner never constructs kernel arguments itself).
    Returns ``None`` for untileable shapes, without touching the cache.
    """
    cands = candidate_blocks(kind, m, n, k, group, rank)
    if not cands:
        return None
    cache = cache or default_cache()
    best, best_t = None, float("inf")
    for blocks in cands:
        try:
            t = _measure(make_call(blocks), iters=iters)
        except Exception:  # a candidate that fails to compile is just skipped
            continue
        if t < best_t:
            best, best_t = blocks, t
    if best is None:
        return None
    cache.store(
        cache_key(kind, m, n, k, group, rank),
        best,
        best_us=round(best_t * 1e6, 2),
        candidates=len(cands),
    )
    return best


def _cli() -> None:
    """Measured-sweep CLI (run on the serving hardware)::

        python -m repro.kernels.autotune dual_prefill --m 1024 --n 4096 --k 4096
        python -m repro.kernels.autotune dual_decode  --m 8 --n 14336 --k 4096

    Builds a random layer at the given shape, times every candidate block
    triple, and persists the winner to the tune cache the dispatch layer
    reads (artifacts/tune/<kind>.json).
    """
    import argparse

    ap = argparse.ArgumentParser(description=_cli.__doc__)
    ap.add_argument("kind", choices=["dual_prefill", "dual_decode", "w4a16"])
    ap.add_argument("--m", type=int, required=True)
    ap.add_argument("--n", type=int, required=True)
    ap.add_argument("--k", type=int, required=True)
    ap.add_argument("--group", type=int, default=128)
    ap.add_argument("--rank", type=int, default=128)
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args()

    import jax.numpy as jnp

    from repro.kernels.ref import (
        pack_rows_groupsplit,
        pack_twinquant_weights,
        quantize_rows_ref,
    )

    key = jax.random.PRNGKey(0)
    k1, k2, k3, k4 = jax.random.split(key, 4)
    interpret = jax.default_backend() == "cpu"
    x = jax.random.normal(k4, (args.m, args.k)).astype(jnp.bfloat16)

    if args.kind == "w4a16":
        from repro.kernels.w4a16_gemm import w4a16_gemm

        wq, ws = quantize_rows_ref(
            jax.random.normal(k1, (args.k, args.n)) * 0.1, args.group, 4
        )
        wp = pack_rows_groupsplit(wq, args.group)

        def make_call(blocks):
            bm, bn, bk = blocks
            pad = (-args.m) % bm
            xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
            return lambda: w4a16_gemm(
                xp, wp, ws, group=args.group,
                block_m=bm, block_n=bn, block_k=bk, interpret=interpret,
            )
    else:
        from repro.kernels.twinquant_dual_gemm import dual_gemm
        from repro.kernels.twinquant_dual_gemv import dual_gemv

        w = pack_twinquant_weights(
            jax.random.normal(k1, (args.k, args.rank)) * 0.1,
            jax.random.normal(k2, (args.rank, args.n)) * 0.1,
            jax.random.normal(k3, (args.k, args.n)) * 0.05,
            group=args.group,
        )

        def make_call(blocks):
            bm, bn, bk = blocks
            if args.kind == "dual_decode":
                return lambda: dual_gemv(x, w, block_n=bn, interpret=interpret)
            pad = (-args.m) % bm
            xp = jnp.pad(x, ((0, pad), (0, 0))) if pad else x
            return lambda: dual_gemm(
                xp, w, block_m=bm, block_n=bn, block_k=bk, interpret=interpret,
            )

    best = autotune_blocks(
        args.kind, make_call, args.m, args.n, args.k, args.group, args.rank,
        iters=args.iters,
    )
    if best is None:
        raise SystemExit(f"shape not tileable: {(args.m, args.n, args.k)}")
    key_str = cache_key(args.kind, args.m, args.n, args.k, args.group, args.rank)
    print(f"{key_str} -> blocks {best} (persisted to {default_cache().dir})")


if __name__ == "__main__":
    _cli()
