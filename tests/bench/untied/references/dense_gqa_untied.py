"""Plain reference of ``architectures/dense_gqa_untied.py``: ``dense_gqa``'s
reference up to the final states, then the untied lm head."""

from __future__ import annotations

from harness import load, model as M

R = load.sibling(__file__, "references", "dense_gqa")
A = load.sibling(__file__, "architectures", "dense_gqa_untied")


def logits_at(seed: int, arch, seqs: list, rows: list, stores: tuple) -> dict:
    xs = R.final_states(seed, arch, seqs, stores)
    key = M.root_key(seed)
    return R.head_logits(xs, rows, A.D.draw_final_norm(key, arch), A.draw_head(key, arch).T,
                         arch)
