"""Is what the timed path served correct? A comparison with the plain
reference, once the window has closed and the program's state is freed.

A sample of the finished requests is drawn from the seed, always with the
longest among them, until it holds ``tokens`` served tokens (or
``max_requests`` requests). The reference (``bench/references/<name>.py``,
named by the configuration) runs once over each prompt followed by its
served tokens. Under greedy decoding each served token should be the
reference's best at its position. At each position the gap is the
reference's best logit minus the reference's logit of the served token.
The numbers compared are those the configuration gives a limit for, of
``logit_gap_trim90`` (the mean of the smallest nine tenths of the gaps),
``mean_logit_gap`` and ``max_logit_gap`` (the widest); PERF.md says why each
limit is where it is.
"""

from __future__ import annotations

import numpy as np


def sample(sent: list, seed: int, conf: dict) -> list:
    """Finished requests to check, drawn from the seed, the longest first."""
    done = [s for s in sent if s.request.status == "DONE" and s.request.out]
    if not done:
        return []
    longest = max(done, key=lambda s: len(s.request.out))
    pick, total = [longest], len(longest.request.out)
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng((int(seed) % (1 << 63)) ^ 0x5EED)
    for i in rng.permutation(len(rest)):
        if total >= conf.get("tokens", 256) or len(pick) >= conf.get("max_requests", 8):
            break
        pick.append(rest[int(i)])
        total += len(rest[int(i)].request.out)
    return pick


def sequences(picked: list) -> tuple:
    """(token sequences, logit rows, served tokens): each prompt followed by
    its served tokens but the last, and the rows whose logits chose them."""
    seqs, rows, served = [], [], []
    for s in picked:
        prompt = np.asarray(s.item.prompt, np.int32)
        out = np.asarray(s.request.out, np.int32)
        seqs.append(np.concatenate([prompt, out[:-1]]))
        rows.append(np.arange(len(prompt) - 1, len(prompt) - 1 + len(out)))
        served.append(out)
    return seqs, rows, served


def gaps(ref_logits: list, chosen: list) -> np.ndarray:
    """Reference best logit minus the reference logit of the chosen token,
    at every checked position."""
    out = [lg.max(axis=-1) - lg[np.arange(len(c)), c] for lg, c in zip(ref_logits, chosen)]
    return np.concatenate(out) if out else np.zeros(0)


def readings(ref, arch, seed: int, picked: list, control: bool = False) -> dict:
    """{"program": gaps of the served tokens, "control": gaps of the tokens
    the lower-precision reference puts first (with ``control``)}; ``ref`` is
    the configuration's reference module."""
    seqs, rows, served = sequences(picked)
    stores = ("bfloat16",) + (("float8_e4m3fn",) if control else ())
    lg = ref.logits_at(seed, arch, seqs, rows, stores)
    base = lg["bfloat16"]
    out = {"program": gaps(base, served)}
    if control:
        out["control"] = gaps(base, [c.argmax(axis=-1) for c in lg["float8_e4m3fn"]])
    return out


def trim90(g: np.ndarray) -> float:
    """Mean of the smallest nine tenths of the gaps: the typical gap, with
    the tenth of positions where 4-bit flips cascaded through a row left
    out (PERF.md, "How correct is decided")."""
    return float(np.sort(g)[: max(1, int(0.9 * len(g)))].mean())


STATS = {"logit_gap_trim90": trim90, "mean_logit_gap": np.mean, "max_logit_gap": np.max}


def compare(conf: dict, picked: list, rd: dict, control: bool = False) -> dict:
    """The numbers compared, each beside its limit, for the program's served
    tokens ({"program": ...}) and, with ``control``, for the tokens that the
    lower-precision control puts first at the same positions ({"control":
    ...}); ``rd`` is what ``readings`` gave for ``picked``. Either is correct
    when every value is within its limit. With ``control`` every statistic
    is read, a limit or not (calibration)."""
    limits = conf["check"]
    served = sum(len(s.request.out) for s in picked)
    base = {"unchecked_tokens": {"value": max(0, limits["min_tokens"] - served), "limit": 0}}
    if not rd:
        return {who: dict(base) for who in ("program", "control")[: 1 + control]}
    return {who: {**base, **{name: {"value": float(stat(g)), "limit": limits.get(name)}
                             for name, stat in STATS.items() if name in limits or control}}
            for who, g in rd.items()}


def correct(compared: dict) -> bool:
    return all(c["value"] <= c["limit"] for c in compared.values() if c["limit"] is not None)
