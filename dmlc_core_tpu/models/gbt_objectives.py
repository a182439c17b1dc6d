"""GBT training objectives and eval metrics (the booster's loss surface).

Functional parity: XGBoost's objective registry (reference
``src/objective/`` family — binary:logistic, multi:softmax/softprob,
reg:squarederror, rank:pairwise; SURVEY.md §1 consumer surface) and its
``eval_metric`` table.  Split out of ``histgbt.py`` so the objective
registry is importable without the tree engine (GBLinear shares it).

Every objective provides ``grad_hess`` (the boosting step's inputs),
``transform`` (margin → prediction), ``row_loss``/``metric`` (training
eval), and ``finalize_mean_loss`` (the external-memory path's
mean-of-sums finalizer).
"""

from __future__ import annotations

from typing import Any, Dict, List, NamedTuple, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from dmlc_core_tpu.base.logging import CHECK, log_fatal
from dmlc_core_tpu.base.registry import Registry

__all__ = ["OBJECTIVES", "EVAL_METRICS", "fold_scale_pos_weight"]

OBJECTIVES: Registry = Registry.get("gbt_objective")


class _ObjectiveBase:
    """Shared objective plumbing: the metric is the mean of per-row
    losses and the external-memory path's finalizer is the identity —
    objectives override only where that isn't true (rmse)."""

    @classmethod
    def metric(cls, pred, y):
        return jnp.mean(cls.row_loss(pred, y))

    @staticmethod
    def finalize_mean_loss(m: float) -> float:
        return m


@OBJECTIVES.register("binary:logistic")
class _Logistic(_ObjectiveBase):
    """grad/hess of log loss on raw margins; transform = sigmoid."""

    @staticmethod
    def grad_hess(pred, y):
        p = jax.nn.sigmoid(pred)
        return p - y, p * (1.0 - p)

    @staticmethod
    def transform(pred):
        return jax.nn.sigmoid(pred)

    @staticmethod
    def row_loss(pred, y):  # per-row logloss
        p = jax.nn.sigmoid(pred)
        eps = 1e-7
        return -(y * jnp.log(p + eps) + (1 - y) * jnp.log(1 - p + eps))


@OBJECTIVES.register("multi:softmax")
class _Softmax(_ObjectiveBase):
    """K-class softmax objective (XGBoost ``multi:softmax``).  Margins are
    CLASS-MAJOR on the device, ``[K, n]``: the rows on the lanes, the
    layout of every other per-row array of the round, and a class's
    gradients one contiguous row (``[n, K]`` exists only at the host's
    edge: ``predict(output_margin=True)``, ``predict_proba``,
    ``train_margins``).  grad/hess per class from the full softmax over a
    row's K margins — the classes are coupled through it — with
    XGBoost's factor 2 on the hessian.  ``predict`` returns the argmax
    class; ``multi:softprob`` is the same training and returns the
    probabilities."""

    @staticmethod
    def grad_hess(pred, y):                  # pred [K, n], y [n] labels
        prob = jax.nn.softmax(pred, axis=0)
        yoh = jax.nn.one_hot(y.astype(jnp.int32), pred.shape[0],
                             dtype=pred.dtype, axis=0)
        return prob - yoh, jnp.maximum(2.0 * prob * (1.0 - prob), 1e-6)

    @staticmethod
    def transform(pred):                     # [K, n] -> class index [n]
        return jnp.argmax(pred, axis=0).astype(jnp.float32)

    @staticmethod
    def prob(pred):                          # [K, n] -> [K, n]
        return jax.nn.softmax(pred, axis=0)

    @staticmethod
    def row_loss(pred, y):                   # mlogloss, [K, n] -> [n]
        logp = jax.nn.log_softmax(pred, axis=0)
        return -jnp.take_along_axis(
            logp, y.astype(jnp.int32)[None, :], axis=0)[0]


@OBJECTIVES.register("multi:softprob")
class _Softprob(_Softmax):
    """``multi:softmax``'s training; ``predict`` returns the ``[n, K]``
    class probabilities (``XGBClassifier``'s default for several
    classes) where ``multi:softmax`` returns the argmax class."""

    transform = _Softmax.prob


@OBJECTIVES.register("reg:squarederror")
class _SquaredError(_ObjectiveBase):
    @staticmethod
    def grad_hess(pred, y):
        return pred - y, jnp.ones_like(pred)

    @staticmethod
    def transform(pred):
        return pred

    @staticmethod
    def row_loss(pred, y):  # per-row squared error
        return (pred - y) ** 2

    @classmethod
    def metric(cls, pred, y):  # rmse = sqrt of the mean row loss
        return jnp.sqrt(jnp.mean(cls.row_loss(pred, y)))

    @staticmethod
    def finalize_mean_loss(m: float) -> float:
        return float(np.sqrt(m))


#: pair slots a block of one bucket may hold (queries a block x width^2):
#: the budget is of SLOTS, not of queries — 256 queries of width 2,048
#: would be 1.07G.  2^22 float32 slots are 16 MiB a pair tensor, and the
#: LambdaMART weights keep five or six alive at once.
RANK_PAIR_SLOTS = 1 << 22
#: below this width a bucket's blocks carry the QUERIES on the lanes
#: (``[W, W, queries]``): a ``[queries, 8, 8]`` block would fill 8 of a
#: vector register's 128 lanes
_LANES = 128


def rank_width(n_docs: int) -> int:
    """The bucket width of a query of ``n_docs`` documents: the smallest
    step of the ladder that holds it — half octaves in whole sublanes: 8,
    16, 24, 32, 48, 64, 96, 128, 192, 256, 384, 512, 768, 1024, 1536,
    2048, ...  A whole octave a step computes 1.7 slots for a pair of the
    data, half octaves 1.3 (a query is at worst 1.5 x 1.5 = 2.25 times
    its own pairs, not 4); the programs' worth of shapes doubles (17 up
    to 2,048) and stays small beside the round's."""
    w = 8
    while True:
        for cand in (w, w + w // 2):
            if cand >= n_docs and cand % 8 == 0:
                return cand
        w *= 2


class RankBucket(NamedTuple):
    """One width bucket of a group table, as the program needs it."""
    width: int
    queries: int        # queries a shard, pad queries included
    block: int          # queries a block of the pair sums (divides ``queries``)


class RankGroups(NamedTuple):
    """The STATIC part of a handle's group table: what shapes the round
    program (hashable: part of its cache key).  The arrays that go with
    it (:meth:`_PairwiseRank.table_specs`) ride as operands."""
    buckets: Tuple[RankBucket, ...]
    #: sum over the data's queries of G_q^2 and what the buckets compute
    pairs: int
    pair_slots: int

    def describe(self) -> Dict[str, Any]:
        return {"rank_buckets": [[b.width, b.queries] for b in self.buckets],
                "rank_pair_slots": self.pair_slots,
                "rank_pairs": self.pairs}


def rank_buckets(lens_by_shard: Sequence[np.ndarray],
                 pair_slots: int = RANK_PAIR_SLOTS
                 ) -> Tuple[RankGroups, List[List[np.ndarray]]]:
    """Sort every shard's queries (``lens_by_shard[k]``: documents of
    each, in row order) into width buckets.  Returns the static table
    and, per bucket, per shard, the indices of that shard's queries in
    it.  Every shard gets the same bucket shapes (the largest count over
    the shards, rounded up to whole blocks): one program serves a mesh."""
    widths = [np.asarray([rank_width(int(n)) for n in lens], np.int64)
              for lens in lens_by_shard]
    ladder = sorted({int(w) for ws in widths for w in ws})
    if ladder:
        # the widest bucket stops at its longest query, in whole sublanes
        # (whole registers from 128 up): MSLR's 1,251 is 1,280, not 1,536
        longest = max(int(l.max()) for l in lens_by_shard if len(l))
        unit = _LANES if longest > _LANES else 8
        top = -(-longest // unit) * unit
        widths = [np.minimum(ws, top) for ws in widths]
        ladder = sorted({min(w, top) for w in ladder})
    buckets, members = [], []
    for w in ladder:
        member = [np.flatnonzero(ws == w) for ws in widths]
        count = max(len(m) for m in member)
        block = max(1, pair_slots // (w * w))
        if w < _LANES:
            # queries on the lanes: whole registers where there are enough
            block = max(_LANES, block // _LANES * _LANES)
            block = min(block, -(-count // 8) * 8)
        else:
            block = min(block, count)
        buckets.append(RankBucket(w, -(-count // block) * block, block))
        members.append(member)
    n_shards = max(len(lens_by_shard), 1)
    return RankGroups(
        tuple(buckets),
        pairs=int(sum(int((np.asarray(l, np.int64) ** 2).sum())
                      for l in lens_by_shard)),
        pair_slots=int(sum(n_shards * b.queries * b.width * b.width
                           for b in buckets))), members


class _PairAxes(NamedTuple):
    """Where the two documents of a pair lie in a block's pair tensors.
    A block's per-document arrays are 2-D, documents along axis ``i``:
    ``[queries, W]`` makes ``[queries, W_i, W_j]`` (``i, j = 1, 2``),
    ``[W, queries]`` makes ``[W_i, W_j, queries]`` (``0, 1``)."""
    i: int
    j: int

    def of_i(self, x):
        return jnp.expand_dims(x, self.j)

    def of_j(self, x):
        return jnp.expand_dims(x, self.i)

    def of_query(self, x):
        return jnp.expand_dims(x, (self.i, self.j))

    def sum_j(self, x):                  # a per-document array, by i
        return x.sum(axis=self.j)

    def sum_i(self, x):                  # a per-document array, by j
        return x.sum(axis=self.i)


_QUERY_MAJOR = _PairAxes(1, 2)
_QUERY_MINOR = _PairAxes(0, 1)


def _ranks(ax: _PairAxes, s, valid):
    """Rank of every document of a block under the current scores, over
    its WHOLE query (0 = best): the documents ahead of it, counted.
    THE RULE FOR TIES: descending score, then position in the query —
    what a stable sort by ``-score`` gives.  Pad slots are ahead of
    nothing, so the real documents' ranks are those of the query alone
    whatever the bucket's width."""
    pos = jax.lax.broadcasted_iota(jnp.int32, s.shape, ax.i)
    si, sj = ax.of_i(s), ax.of_j(s)
    ahead = ax.of_j(valid) & ((sj > si) | (
        (sj == si) & (ax.of_j(pos) < ax.of_i(pos))))
    return ax.sum_j(ahead.astype(jnp.int32))


@OBJECTIVES.register("rank:pairwise")
class _PairwiseRank(_ObjectiveBase):
    """RankNet-style pairwise ranking over ``qid`` groups (XGBoost
    ``rank:pairwise`` — the consumer of the data plane's qid column,
    reference ``data.h :: Row::qid``, SURVEY.md §2a).

    Contract with :meth:`HistGBT.make_device_data`: rows arrive in QUERY
    ORDER, each query's documents consecutive and in their input order,
    ragged — no query is padded to another's length and none is cut —
    and shard boundaries fall on query boundaries, so each device's
    shard is whole queries and the pairwise gradients are shard-local
    (no cross-device pairs; the histogram psum is the only collective,
    unchanged).  The handle's GROUP TABLE says where the queries lie:
    its static part is this objective's configuration
    (:class:`RankGroups`, from :func:`rank_buckets`), its arrays
    (:meth:`table_specs`) are operands of the round program.

    The gradient works in WIDTH BUCKETS.  Queries are sorted into the
    ladder of :func:`rank_width`; a bucket's scores are gathered from
    row order as ``[queries, width]`` (row ``start + j`` in slot ``j``,
    the slots past a query's length masked: their relevance reads -1),
    the pair sums run over blocks of at most :data:`RANK_PAIR_SLOTS`
    pair slots (a budget of slots, not of queries), and ``g``, ``h`` go
    back to row order together, every row reading its own slot
    (``slot``: a gather, which the chip does faster than the scatter it
    stands for — PERF.md section 6, PR 44).  The table is static for a
    handle, so a fit's program compiles once.

    Per better-pair (i, j) with rel_i > rel_j inside one query:
    ``p = σ(s_j − s_i)``; ``g_i −= p·w``, ``g_j += p·w``, and both
    documents accumulate hessian ``p(1−p)·w``, floored at 1e-16; ``w`` is
    1 here and |Δmetric| of swapping the pair in the subclasses.  A query
    of one document, or of one relevance level, has no pair: ``g = 0``,
    ``h = 1e-16``.  Ranks are over the whole query, ties by
    :func:`_ranks`' rule.
    """

    is_ranking = True

    def __init__(self, groups: RankGroups):
        self.groups = groups

    # -- the group table ---------------------------------------------------
    @classmethod
    def from_queries(cls, lens_by_shard: Sequence[np.ndarray],
                     rel_by_shard: Sequence[np.ndarray], rows_a_shard: int,
                     pair_slots: int = RANK_PAIR_SLOTS):
        """The objective configured for these queries, and the arrays of
        its group table on the host (every shard's part laid end to end,
        as ``P("data")`` cuts them).  ``lens_by_shard[k]`` are the
        documents of shard ``k``'s queries in row order, ``rel_by_shard
        [k]`` its rows' relevances (the queries' documents first, then
        pad rows up to ``rows_a_shard``, which no query owns)."""
        groups, members = rank_buckets(lens_by_shard, pair_slots)
        n_shards = len(lens_by_shard)
        starts = [np.cumsum(l) - l for l in lens_by_shard]
        # every row's slot in its shard's buckets laid end to end; a pad
        # row reads the slot past the last
        slots_a_shard = sum(b.queries * b.width for b in groups.buckets)
        slot_of = np.full((n_shards, rows_a_shard), slots_a_shard, np.int32)
        table = {"start": [], "rel": [], "scale": []}
        first_slot = 0
        for b, member in zip(groups.buckets, members):
            start = np.zeros((n_shards, b.queries), np.int32)
            rel = np.full((n_shards, b.queries, b.width), -1.0, np.float32)
            slot = np.arange(b.width)
            for k, q in enumerate(member):
                start[k, :len(q)] = starts[k][q]
                inside = slot[None, :] < lens_by_shard[k][q][:, None]
                rows = (starts[k][q][:, None] + slot[None, :])[inside]
                rel[k, :len(q)][inside] = rel_by_shard[k][rows]
                slot_of[k, rows] = (
                    first_slot + np.arange(len(q))[:, None] * b.width
                    + slot[None, :])[inside]
            first_slot += b.queries * b.width
            rel = rel.reshape(-1, b.width)
            table["start"].append(start.reshape(-1))
            table["rel"].append(rel)
            table["scale"].append(cls.query_scale(rel))
        return cls(groups), {**{k: tuple(v) for k, v in table.items()},
                             "slot": slot_of.reshape(-1)}, members

    @staticmethod
    def query_scale(rel: np.ndarray) -> np.ndarray:
        """Per-query factor of the pair weights, from a bucket's padded
        relevances ``[queries, width]`` (pads -1): static for a handle,
        so it is computed once, in float64, on the host."""
        return np.ones(len(rel), np.float32)

    def table_specs(self):
        """``PartitionSpec`` of every array of the table, as a pytree."""
        return {"start": tuple(P("data") for _ in self.groups.buckets),
                "rel": tuple(P("data", None) for _ in self.groups.buckets),
                "scale": tuple(P("data") for _ in self.groups.buckets),
                "slot": P("data")}

    def table_structs(self, mesh, n_rows: int):
        """Shape, dtype and sharding of every array of the table of a
        handle of ``n_rows`` rows over ``mesh`` (what the round program
        is lowered against)."""
        k = int(mesh.shape["data"])

        def struct(shape, dtype, spec):
            return jax.ShapeDtypeStruct(
                shape, dtype, sharding=NamedSharding(mesh, spec))

        bs = self.groups.buckets
        return {
            "start": tuple(struct((k * b.queries,), np.int32, P("data"))
                           for b in bs),
            "rel": tuple(struct((k * b.queries, b.width), np.float32,
                                P("data", None)) for b in bs),
            "scale": tuple(struct((k * b.queries,), np.float32, P("data"))
                           for b in bs),
            "slot": struct((n_rows,), np.int32, P("data"))}

    # -- the stage ---------------------------------------------------------
    def _pair_weight(self, ax, s, r, valid, scale):
        """Per-pair lambda weight (a block's pair tensor) or None
        (unweighted RankNet).  The LambdaMART subclasses return
        |Δmetric| of swapping the pair in the current ranking."""
        return None

    def _block_pairs(self, ax: _PairAxes, s, r):
        """``s_i − s_j`` and the better-pair mask of one block."""
        valid = r >= 0
        S = ax.of_i(s) - ax.of_j(s)
        better = ((ax.of_i(r) > ax.of_j(r))
                  & ax.of_i(valid) & ax.of_j(valid))
        return S, better, valid

    def _block_grad_hess(self, ax: _PairAxes, s, r, scale):
        S, better, valid = self._block_pairs(ax, s, r)
        lam = jnp.where(better, jax.nn.sigmoid(-S), 0.0)
        rho = lam * (1.0 - lam)
        w = self._pair_weight(ax, s, r, valid, scale)
        if w is not None:
            lam = lam * w
            rho = rho * w
        g = -ax.sum_j(lam) + ax.sum_i(lam)                  # winner/loser
        h = ax.sum_j(rho) + ax.sum_i(rho)
        return g, h

    def _map_buckets(self, pred, table, block_fn):
        """Shared scaffolding: per bucket, the scores gathered from row
        order as ``[queries, width]``, ``lax.map`` of ``block_fn(ax, s,
        r, scale)`` over the bucket's blocks, under the bucket's scope.
        Returns every bucket's per-block results: the gradients and the
        loss derive from exactly these tensors, so the masking rules
        live in ONE place."""
        outs = []
        for b, start, rel, scale in zip(self.groups.buckets, table["start"],
                                        table["rel"], table["scale"]):
            with jax.named_scope(f"dmlc.round.grad.rank.w{b.width}"):
                rows = start[:, None] + jnp.arange(b.width, dtype=jnp.int32)
                # a slot past the shard's last row reads 0 (and is masked)
                s = pred.at[rows].get(mode="fill", fill_value=0.0)
                nb = b.queries // b.block
                minor = b.width < _LANES
                ax = _QUERY_MINOR if minor else _QUERY_MAJOR

                def block(args, ax=ax, minor=minor):
                    sb, rb, cb = args                       # [block, W]
                    if minor:
                        sb, rb = sb.T, rb.T
                    return block_fn(ax, sb, rb, cb)

                outs.append(jax.lax.map(block, (
                    s.reshape(nb, b.block, b.width),
                    rel.reshape(nb, b.block, b.width),
                    scale.reshape(nb, b.block))))
        return outs

    def grad_hess(self, pred, y, table):
        """``(g, h)`` of a shard's rows ``pred`` (row order) under its
        group ``table``; ``y`` is not read (the table holds the
        relevances by slot)."""
        del y
        with jax.named_scope("dmlc.round.grad.rank"):
            by_slot = []
            for b, (g, h) in zip(self.groups.buckets, self._map_buckets(
                    pred, table, self._block_grad_hess)):
                gh = jnp.stack([g, h], axis=-1)       # [blocks, .., .., 2]
                if b.width < _LANES:
                    gh = gh.transpose(0, 2, 1, 3)
                by_slot.append(gh.reshape(b.queries * b.width, 2))
            # a pad row reads the last slot, which no query owns
            by_slot.append(jnp.zeros((1, 2), jnp.float32))
            gh_rows = jnp.concatenate(by_slot)[table["slot"]]
            # docs with no pairs get h=0 → leaf math guards with +lambda,
            # but keep hessians nonnegative-and-tiny like XGBoost's floor
            return gh_rows[:, 0], jnp.maximum(gh_rows[:, 1], 1e-16)

    @staticmethod
    def transform(pred):
        return pred

    def row_loss(self, pred, y):  # pairwise logloss, averaged per pair
        log_fatal("rank objectives have no per-row loss: the mean pairwise "
                  "loss needs the group table (loss_sums; a group-aware "
                  "ndcg/map eval lives in models.ranking, on predictions)")

    def loss_sums(self, pred, table):
        """(summed pairwise logistic loss, better-pairs) of a shard —
        same bucket scaffolding as grad_hess, one masking rule."""
        def block_fn(ax, s, r, scale):
            S, better, _ = self._block_pairs(ax, s, r)
            return (jnp.where(better, jnp.logaddexp(0.0, -S), 0.0).sum(),
                    better.sum())

        loss = jnp.float32(0.0)
        count = jnp.int32(0)
        for losses, counts in self._map_buckets(pred, table, block_fn):
            loss = loss + losses.sum()
            count = count + counts.sum()
        return loss, count


@OBJECTIVES.register("rank:ndcg")
class _NDCGRank(_PairwiseRank):
    """LambdaMART over NDCG (XGBoost ``rank:ndcg``): each better-pair's
    RankNet lambda is weighted by |ΔNDCG| — the change in the query's
    NDCG if the two docs swapped places in the CURRENT ranking — so
    gradient mass concentrates on misorderings near the top of the list
    (Burges' LambdaMART; the delta uses the standard exp2 gain and
    log2 position discount over the full group, no truncation level).

    Pad slots (rel −1) are ahead of no document and carry zero gain, so
    they contribute no weight; a query with IDCG 0 (all rel 0) has no
    better-pairs to weight.  1 / IDCG depends on the labels alone: it is
    the table's ``scale``.
    """

    @staticmethod
    def query_scale(rel):
        rel = np.asarray(rel, np.float64)
        best = -np.sort(-rel, axis=1)                       # ideal order
        igain = np.where(best >= 0, np.exp2(best) - 1.0, 0.0)
        disc = 1.0 / np.log2(2.0 + np.arange(rel.shape[1]))
        idcg = (igain * disc[None, :]).sum(axis=1)
        return np.where(idcg > 0.0, 1.0 / np.where(idcg > 0.0, idcg, 1.0),
                        0.0).astype(np.float32)

    def _pair_weight(self, ax, s, r, valid, scale):
        f32 = s.dtype
        disc = 1.0 / jnp.log2(2.0 + _ranks(ax, s, valid).astype(f32))
        gain = jnp.where(valid, jnp.exp2(r) - 1.0, 0.0)
        # swapping i and j moves gain_i to disc_j and vice versa:
        # |ΔDCG| = |g_i − g_j| · |d_i − d_j|
        return (jnp.abs(ax.of_i(gain) - ax.of_j(gain))
                * jnp.abs(ax.of_i(disc) - ax.of_j(disc))
                * ax.of_query(scale))


@OBJECTIVES.register("rank:map")
class _MAPRank(_PairwiseRank):
    """LambdaMART over MAP (XGBoost ``rank:map``, binary relevance:
    rel > 0 counts as relevant): lambdas weighted by |ΔAP| of swapping
    the pair in the current ranking.

    Closed form (positions a < b in score order, prefix counts
    ``c_p = #relevant ≤ p``, ``T_p = Σ_{q≤p} rel_q/(q+1)``, swap shift
    ``s = rel_b − rel_a``; only positions in [a, b] change):

        R·ΔAP = (rel_b·(c_a + s) − rel_a·c_a)/(a+1)
              + (rel_a − rel_b)·c_b/(b+1)
              + s·(T_{b−1} − T_a)

    verified against a brute-force swap-and-rescore in
    ``tests/test_ranking.py``.  The prefix sums are taken per document by
    counting (the relevant documents ranked no later), so nothing is
    sorted; 1 / R is the table's ``scale``.
    """

    @staticmethod
    def query_scale(rel):
        R = (np.asarray(rel) > 0).sum(axis=1).astype(np.float64)
        return np.where(R > 0, 1.0 / np.maximum(R, 1.0),
                        0.0).astype(np.float32)

    def _pair_weight(self, ax, s, r, valid, scale):
        f32 = s.dtype
        rel = jnp.where(valid, (r > 0.0).astype(f32), 0.0)
        ranks = _ranks(ax, s, valid)
        P_ = (ranks + 1).astype(f32)                        # 1-based pos
        # per-DOC prefix sums at the doc's own rank position
        no_later = (ax.of_j(ranks) <= ax.of_i(ranks)) & ax.of_j(valid)
        C = ax.sum_j(jnp.where(no_later, ax.of_j(rel), 0.0))
        Td = ax.sum_j(jnp.where(no_later, ax.of_j(rel / P_), 0.0))
        i_first = ax.of_i(ranks) < ax.of_j(ranks)

        def pick(x):                                        # a/b selection
            xi, xj = ax.of_i(x), ax.of_j(x)
            return (jnp.where(i_first, xi, xj),
                    jnp.where(i_first, xj, xi))

        rel_a, rel_b = pick(rel)
        C_a, C_b = pick(C)
        T_a, T_b = pick(Td)
        P_a, P_b = pick(P_)
        sh = rel_b - rel_a
        T_bm1 = T_b - rel_b / P_b
        delta = ((rel_b * (C_a + sh) - rel_a * C_a) / P_a
                 + (rel_a - rel_b) * C_b / P_b
                 + sh * (T_bm1 - T_a))
        return jnp.abs(delta) * ax.of_query(scale)


def fold_scale_pos_weight(param, y, weight):
    """Fold ``param.scale_pos_weight`` into the instance-weight vector.

    XGBoost semantics: positives' grad AND hess scale by the factor —
    definitionally an instance weight.  THE one implementation, shared
    by HistGBT and GBLinear (any booster whose param carries the field
    and an ``objective``), so the two cannot silently diverge.
    """
    if param.scale_pos_weight == 1.0:
        return weight
    CHECK(param.objective == "binary:logistic",
          f"scale_pos_weight only applies to binary:logistic "
          f"(objective is {param.objective!r})")
    spw = np.where(np.asarray(y) == 1.0,
                   np.float32(param.scale_pos_weight), np.float32(1.0))
    return spw if weight is None else np.asarray(weight, np.float32) * spw


def _metric_auc(margin, y):
    """ROC-AUC via the rank-sum (Mann-Whitney) identity with MIDRANKS for
    ties — GBT margins tie heavily (one tree = ≤2^depth distinct values),
    and sort-order ranks would score an all-equal round as ~0/1 instead
    of 0.5.  Degenerate single-class sets return 0.5 (neutral) rather
    than NaN, which would poison the early-stopping comparison."""
    s = jnp.sort(margin)
    lo = jnp.searchsorted(s, margin, side="left")
    hi = jnp.searchsorted(s, margin, side="right")
    midrank = (lo + hi + 1) / 2.0                   # 1-based midranks
    npos = jnp.sum(y)
    nneg = y.shape[0] - npos
    denom = npos * nneg
    auc = (jnp.sum(midrank * y) - npos * (npos + 1) / 2) / jnp.where(
        denom > 0, denom, 1.0)
    return jnp.where(denom > 0, auc, 0.5)


#: eval_metric name → (fn(margin, y) -> scalar, maximize?)
EVAL_METRICS = {
    "logloss": (_Logistic.metric, False),
    "error": (lambda m, y: jnp.mean((jax.nn.sigmoid(m) > 0.5) != (y > 0.5)),
              False),
    "auc": (_metric_auc, True),
    "rmse": (_SquaredError.metric, False),
    "mae": (lambda m, y: jnp.mean(jnp.abs(m - y)), False),
    "mlogloss": (_Softmax.metric, False),
    "merror": (lambda m, y: jnp.mean(
        jnp.argmax(m, axis=0) != y.astype(jnp.int32)), False),
}

#: which metrics make sense for which objective's margin shape
_METRICS_BY_OBJECTIVE = {
    "binary:logistic": {"logloss", "error", "auc"},
    "reg:squarederror": {"rmse", "mae"},
    "multi:softmax": {"mlogloss", "merror"},
    "multi:softprob": {"mlogloss", "merror"},
    # rank eval (ndcg/map) needs qid groups, which EVAL_METRICS'
    # (margin, y) signature can't see — use models.ranking.ndcg on
    # predictions instead; in-training eval reports pairwise loss
    "rank:pairwise": set(),
    "rank:ndcg": set(),
    "rank:map": set(),
}

