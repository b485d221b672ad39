"""The compiled rulebook: device-servable association rules (DESIGN.md §8).

``compile_rulebook`` lowers a mined :class:`~repro.core.apriori.AprioriResult`
into four column arrays — the exact operand format of the rule-match kernel
(``kernels/rule_match.py``):

    ante_packed (R, W) uint32   antecedent bitsets (support_count_packed
    cons_packed (R, W) uint32   consequent bitsets    word layout, §4)
    ante_len    (R,)   int32    antecedent popcounts; -1 = padding row
    scores      (R,)   float32  serving weight (confidence | lift); 0 on padding

Rules are sorted by descending score with a deterministic bitset tie-break,
optionally truncated to ``max_rules``, and padded to ``pad_multiple`` rows
with the standard inert padding (zero words, ``len = -1``, score 0) so the
artifact device-places and shards evenly without re-padding at query time.

``save``/``load`` round-trip the artifact as a single ``.npz``;
``place_rulebook`` device-places the columns sharded over a mesh axis
(`place_db`-style: rules are the rulebook's row axis the way transactions
are the store's), which pairs with the psum-over-rule-shards match step in
``serving/recommend.py``.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.core import rules as rules_mod
from repro.obs.trace import LeafSpan

SCORE_KINDS = ("confidence", "lift")


@dataclasses.dataclass
class Rulebook:
    ante_packed: np.ndarray   # (R, W) uint32
    cons_packed: np.ndarray   # (R, W) uint32
    ante_len: np.ndarray      # (R,)   int32, -1 = padding
    scores: np.ndarray        # (R,)   float32, 0 on padding
    num_items: int
    score_kind: str = "confidence"
    min_confidence: float = 0.0

    @property
    def num_rules(self) -> int:
        """Real (non-padding) rules."""
        return int((np.asarray(self.ante_len) >= 0).sum())

    @property
    def num_rows(self) -> int:
        """Padded row count actually resident on device."""
        return self.ante_packed.shape[0]

    def save(self, path: str) -> None:
        np.savez(
            path,
            ante_packed=np.asarray(self.ante_packed),
            cons_packed=np.asarray(self.cons_packed),
            ante_len=np.asarray(self.ante_len),
            scores=np.asarray(self.scores),
            num_items=np.int64(self.num_items),
            score_kind=np.bytes_(self.score_kind.encode()),
            min_confidence=np.float64(self.min_confidence),
        )

    @classmethod
    def load(cls, path: str) -> "Rulebook":
        with np.load(path) as z:
            return cls(
                ante_packed=z["ante_packed"],
                cons_packed=z["cons_packed"],
                ante_len=z["ante_len"],
                scores=z["scores"],
                num_items=int(z["num_items"]),
                score_kind=bytes(z["score_kind"]).decode(),
                min_confidence=float(z["min_confidence"]),
            )


def compile_rulebook(
    result,
    *,
    min_confidence: float = 0.5,
    score: str = "confidence",
    max_rules: int | None = None,
    num_items: int | None = None,
    pad_multiple: int = 256,
) -> Rulebook:
    """Vectorized extraction (``core.rules.extract_rule_arrays``) -> sorted,
    truncated, padded serving columns, as the host span
    ``repro.rulebook.compile``."""
    if score not in SCORE_KINDS:
        raise ValueError(f"score must be one of {SCORE_KINDS}, got {score!r}")
    with LeafSpan("rulebook", "compile"):
        arr = rules_mod.extract_rule_arrays(result, min_confidence, num_items)
        scores = np.asarray(arr.confidence if score == "confidence" else arr.lift, np.float32)

        # descending score, bitset tie-break (np.lexsort: last key is primary)
        keys = (
            [arr.cons_packed[:, w] for w in range(arr.cons_packed.shape[1] - 1, -1, -1)]
            + [arr.ante_packed[:, w] for w in range(arr.ante_packed.shape[1] - 1, -1, -1)]
            + [-scores.astype(np.float64)]
        )
        order = np.lexsort(keys)
        if max_rules is not None:
            order = order[:max_rules]

        r = order.size
        rp = max(pad_multiple, ((r + pad_multiple - 1) // pad_multiple) * pad_multiple)
        w = arr.ante_packed.shape[1]
        ante = np.zeros((rp, w), np.uint32)
        cons = np.zeros((rp, w), np.uint32)
        lens = np.full(rp, -1, np.int32)
        sc = np.zeros(rp, np.float32)
        ante[:r] = arr.ante_packed[order]
        cons[:r] = arr.cons_packed[order]
        lens[:r] = arr.ante_len[order]
        sc[:r] = scores[order]
        return Rulebook(ante, cons, lens, sc, arr.num_items, score, min_confidence)


def place_rulebook(rb: Rulebook, mesh, rule_axis: str = "model") -> Rulebook:
    """Device-place the rulebook columns sharded over ``rule_axis`` — the
    serving twin of ``core.apriori.place_db``.  Rows are padded (inertly) to
    the shard count first so ``P(rule_axis)`` always splits evenly.  With
    ``mesh is None`` the columns are simply committed to the default device.
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    if mesh is None:
        return Rulebook(
            jnp.asarray(rb.ante_packed), jnp.asarray(rb.cons_packed),
            jnp.asarray(rb.ante_len), jnp.asarray(rb.scores),
            rb.num_items, rb.score_kind, rb.min_confidence,
        )
    shards = mesh.shape[rule_axis]
    pad = (-rb.num_rows) % shards
    ante = np.pad(np.asarray(rb.ante_packed), ((0, pad), (0, 0)))
    cons = np.pad(np.asarray(rb.cons_packed), ((0, pad), (0, 0)))
    lens = np.pad(np.asarray(rb.ante_len), (0, pad), constant_values=-1)
    sc = np.pad(np.asarray(rb.scores), (0, pad))
    row2d, row1d = NamedSharding(mesh, P(rule_axis, None)), NamedSharding(mesh, P(rule_axis))
    return Rulebook(
        jax.device_put(ante, row2d), jax.device_put(cons, row2d),
        jax.device_put(lens, row1d), jax.device_put(sc, row1d),
        rb.num_items, rb.score_kind, rb.min_confidence,
    )
