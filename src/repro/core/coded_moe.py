"""One chip's share of a mixture-of-experts layer through the coded runtime.

Expert parallelism over ``n_shares`` chips: share i holds the routed experts
[i * held, (i + 1) * held) and runs the shared experts for its own slice i
of the tokens; the router still scores every expert for every token.  The
shares' outputs add up to the whole layer's.  A parity block may only mix
rows of one expert, since each expert multiplies its own tokens, so every
expert is coded by the same row code, and the columns of one product hold
every expert's tokens in groups.  A request of one layer:

1. route (scope ``moe.route``): float32 router logits, the scores, top-k
   and weights of ``models.moe.route``; the assignments to each held
   expert are counted and sorted by expert, and the counts read back to
   the host (span ``moe.size``, the layer's one readback), which picks the
   column buffer;
2. dispatch (``moe.route``): each assignment to a held expert, and each
   token of this share's slice for each shared expert (weight 1), gets a
   column of the buffer.  Columns are grouped by expert (held experts in
   order, then the shared ones), in token order, each group padded to
   whole column tiles (``group_tile``); the buffer is a size of a fixed
   ladder (``buffer_columns``), so nothing is ever dropped;
3. the coded gate/up product of every group in one grouped kernel call
   (``coded_matmul.run``), the lost shard's blocks never arriving, then
   ``recover``;
4. SwiGLU (``moe.swiglu``), then the coded down product and its recovery
   the same way;
5. combine (``moe.combine``): each token gathers its held assignments'
   columns times their weights, and its shared experts' columns, into the
   (d, T) output.

Columns are gathered as rows of the transposes (a TPU gathers whole rows,
not single lanes).

Two shared experts of width f are one SwiGLU of width 2f split in halves
whose outputs add, so each is a group of its own.
"""

from __future__ import annotations

import dataclasses
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.coded_matmul.kernel import LANE
from ..models.moe import route
from ..utils import tracing
from . import coded_matmul as cm

__all__ = ["ExpertShare", "Columns", "CodedExperts", "buffer_columns",
           "group_tile", "pack"]

HIGHEST = jax.lax.Precision.HIGHEST
MAX_TILE = 1024


@dataclasses.dataclass(frozen=True)
class ExpertShare:
    """Share ``share`` of a layer whose experts are spread over ``n_shares``
    chips."""

    d_model: int
    d_expert: int
    n_experts: int            # routed experts the router scores
    top_k: int
    n_shared: int             # shared experts, each d_expert wide
    share: int
    n_shares: int
    scoring: str              # softmax | sigmoid (``models.moe.route``)
    scale: float              # routed_scaling_factor

    @property
    def held(self) -> int:
        return self.n_experts // self.n_shares

    @property
    def first(self) -> int:
        return self.share * self.held

    def shared_slice(self, tokens: int) -> slice:
        """The tokens this share runs the shared experts for."""
        n = tokens // self.n_shares
        return slice(self.share * n, (self.share + 1) * n)


class Columns(NamedTuple):
    """Where each column of a request's buffer comes from."""

    tok: jnp.ndarray          # (P,) token of each column; T on padding
    assign: jnp.ndarray       # (T, k) column of each held assignment, else P
    shared_start: jnp.ndarray  # (n_shared,) first column of each shared group
    tile_group: jnp.ndarray   # (P // bn,) group (local expert) of each tile
    live_tiles: jnp.ndarray   # (1,) tiles that hold columns
    stats: jnp.ndarray        # (3,) routed, shared and live-tile columns


def group_tile(tokens: int, top_k: int, n_experts: int) -> int:
    """The column tile each expert's group is padded to: the widest
    power-of-two multiple of 128 columns, up to ``MAX_TILE``, that is at
    most a third of a routed expert's mean load (tokens * top_k /
    n_experts), and at least 128.  A group pads half a tile on average,
    while every tile is one pass of the kernel over all the code's blocks
    and neighbour slots, most of them padded slots that still take a grid
    step: narrow tiles cut padding and multiply steps.  Moonlight's
    32768-token batch (3072 columns an expert) gets 1024, where 512
    would double the grid steps."""
    tile, limit = LANE, min(MAX_TILE, tokens * top_k / n_experts / 3)
    while 2 * tile <= limit:
        tile *= 2
    return tile


def buffer_columns(cols: int, bn: int) -> int:
    """The column buffer for ``cols`` columns: the smallest size of the
    ladder bn * ceil(2 ** (i / 4)), i = 0, 1, ..., that holds them.  Sizes
    step by about 19%, so a layer's routings need few programs, each
    compiled in a few seconds; the dead tiles past the last group cost the
    kernel no copy and the recovery a few percent more columns."""
    tiles = max(1, -(-cols // bn))
    i = max(0, math.floor(4 * math.log2(tiles)) - 1)
    while math.ceil(2 ** (i / 4)) < tiles:
        i += 1
    return bn * math.ceil(2 ** (i / 4))


def pack(gate, up, down, shared_gate, shared_up, shared_down):
    """The coded layer's weights from the published layout: ``gate``/``up``
    (held, f, d) and ``down`` (held, d, f) of the held experts, the shared
    experts' (n_shared * f, d) / (d, n_shared * f).  Returns ``w_gu``
    (held + n_shared, 2f, d), each row block [gate; up], and ``w_down``
    (held + n_shared, d, f); shared expert s is the s-th f-wide half."""
    f = gate.shape[1]
    n_shared = shared_gate.shape[0] // f
    sg = shared_gate.reshape(n_shared, f, -1)
    su = shared_up.reshape(n_shared, f, -1)
    sd = shared_down.reshape(shared_down.shape[0], n_shared, f).swapaxes(0, 1)
    w_gu = jnp.concatenate([jnp.concatenate([gate, up], axis=1),
                            jnp.concatenate([sg, su], axis=1)])
    return w_gu, jnp.concatenate([down, sd])


def _take_columns(x, idx):
    """x[:, idx], zeros where idx is out of range, as a gather of rows of
    x's transpose: a TPU gathers whole rows, not single lanes."""
    return jnp.take(x.T, idx, axis=0, mode="fill", fill_value=0).T


class CodedExperts:
    """The coded expert layer of one share.

    ``n_shards`` logical shards hold the coded blocks of both products, one
    code each (``overhead``; ``bm_gate_up`` and ``bm_down`` rows a block);
    the kernel's column tile, the quantum each group is padded to, comes
    from the shapes (``tile``).
    Call it with a layer's parameters (``router`` (E, d) f32, ``bias`` (E,)
    f32, ``w_gu``, ``w_down`` as ``pack`` lays them out), x (d, T) and the
    lost shard.  Each program is compiled once per column buffer size and
    kept (``programs`` gives their HLO text)."""

    def __init__(self, share: ExpertShare, *, n_shards: int, overhead: float,
                 bm_gate_up: int, bm_down: int,
                 use_pallas: bool = False, interpret: bool = False):
        self.share = share
        self.n_shards = n_shards
        self.plan_gu = cm.plan_coded_matmul(
            rows=2 * share.d_expert, n_shards=n_shards, overhead=overhead,
            bm=bm_gate_up)
        self.plan_dn = cm.plan_coded_matmul(
            rows=share.d_model, n_shards=n_shards, overhead=overhead,
            bm=bm_down)
        self.kernel = dict(use_pallas=use_pallas, interpret=interpret)
        self._jit = {
            "moe_route": jax.jit(self.moe_route),
            "moe_gate_up": jax.jit(self.moe_gate_up,
                                   static_argnames="columns"),
            "moe_down": jax.jit(self.moe_down),
            "moe_combine": jax.jit(self.moe_combine),
        }
        self._compiled = {}

    def tile(self, tokens: int) -> int:
        """The column tile for requests of ``tokens`` tokens."""
        s = self.share
        return group_tile(tokens, s.top_k, s.n_experts)

    # -- the programs -------------------------------------------------------

    def moe_route(self, router, bias, x):
        """Weights and ids (T, k) of the chosen experts, the count of
        assignments to each held expert, and the assignments (flat index t
        k + j) sorted by held expert, in token order within one (the
        others after them)."""
        s = self.share
        with jax.named_scope("moe.route"):
            logits = jnp.einsum("dt,ed->te", x.astype(jnp.float32),
                                router.astype(jnp.float32), precision=HIGHEST)
            w, ids, _ = route(logits, s.top_k, scoring=s.scoring, bias=bias,
                              scale=s.scale)
            local = ids.reshape(-1) - s.first
            counts = (local[:, None] == jnp.arange(s.held)).sum(0)
            # Unique keys, so the unstable sort is the stable one (a TPU
            # compiles a stable sort of this size in ~20 s, this in ~4 s).
            n = local.shape[0]
            held = jnp.where((local >= 0) & (local < s.held), local, s.held)
            order = jax.lax.sort(held * n + jnp.arange(n), is_stable=False) % n
        return w, ids, counts.astype(jnp.int32), order.astype(jnp.int32)

    def _dispatch(self, ids, counts, order, tokens, columns):
        s, bn = self.share, self.tile(tokens)
        held, groups = s.held, s.held + s.n_shared
        sl = s.shared_slice(tokens)
        n_sh = sl.stop - sl.start
        sizes = jnp.concatenate([counts, jnp.full((s.n_shared,), n_sh,
                                                  jnp.int32)])
        padded = (sizes + bn - 1) // bn * bn
        ends = jnp.cumsum(padded)
        starts = ends - padded
        offsets = jnp.cumsum(counts) - counts       # of each group in order
        # A TPU gathers scalars slowly, so picks from the per-group tables
        # are one-hot sums; the only gathers are of whole rows and of
        # ``order``.
        pick = lambda onehot, table: (onehot * table).sum(-1)
        # Each held assignment's column: its group's start plus its rank
        # among the group's tokens.
        local = ids - s.first                                 # (T, k)
        is_g = (local[..., None] == jnp.arange(held)).astype(jnp.int32)
        cum = jnp.cumsum(is_g.sum(1), axis=0)                 # (T, held)
        assign = pick(is_g, starts[:held] + cum[:, None, :] - 1)
        assign = jnp.where(is_g.sum(-1) > 0, assign, columns)
        # Each column's group g and place r in it: a routed column holds
        # the group's r-th assignment in ``order``, a shared one the
        # slice's r-th token.
        col = jnp.arange(columns, dtype=jnp.int32)
        grp = jnp.minimum((col[:, None] >= ends).sum(-1), groups - 1)
        in_g = (grp[:, None] == jnp.arange(groups)).astype(jnp.int32)
        r = col - pick(in_g, starts)
        a = order[jnp.clip(pick(in_g[:, :held], offsets) + r, 0,
                           order.shape[0] - 1)]
        tok = jnp.where(grp < held, a // s.top_k, sl.start + r)
        tok = jnp.where((col < ends[-1]) & (r < pick(in_g, sizes)), tok,
                        tokens)
        tile_group = grp[::bn].astype(jnp.int32)
        live = (ends[-1] // bn).astype(jnp.int32)[None]
        stats = jnp.stack([counts.sum(), jnp.int32(s.n_shared * n_sh),
                           ends[-1]]).astype(jnp.int32)
        return Columns(tok.astype(jnp.int32), assign.astype(jnp.int32),
                       starts[held:].astype(jnp.int32), tile_group, live,
                       stats)

    def _coded(self, plan, a, x, cols, lost):
        """The coded blocks' products; those of shard ``lost`` never
        arrive."""
        bn = x.shape[1] // cols.tile_group.shape[0]
        out = cm.run(plan, a, x, tile_group=cols.tile_group,
                     live_tiles=cols.live_tiles, bn=bn, **self.kernel)
        rows = plan.blocks_per_shard * plan.bm
        return jax.lax.dynamic_update_slice(
            out, jnp.zeros((rows, out.shape[1]), out.dtype),
            (lost * rows, jnp.int32(0)))

    def moe_gate_up(self, x, ids, counts, order, w_gu, lost, *, columns):
        """Dispatch into ``columns`` columns and the coded gate/up product."""
        with jax.named_scope("moe.route"):
            cols = self._dispatch(ids, counts, order, x.shape[1], columns)
            xg = _take_columns(x, cols.tok)
        return self._coded(self.plan_gu, w_gu, xg, cols, lost), cols

    def moe_down(self, gu, w_down, cols, lost):
        """SwiGLU of the recovered gate/up, then the coded down product."""
        f = self.share.d_expert
        with jax.named_scope("moe.swiglu"):
            g, u = gu[:f].astype(jnp.float32), gu[f:].astype(jnp.float32)
            h = (jax.nn.silu(g) * u).astype(gu.dtype)
        return self._coded(self.plan_dn, w_down, h, cols, lost)

    def moe_combine(self, y, w, cols):
        """Each token's output: its held assignments' columns times their
        weights, and the shared experts' columns for this share's slice."""
        s = self.share
        tokens = w.shape[0]
        sl = s.shared_slice(tokens)
        with jax.named_scope("moe.combine"):
            y_t = y.T
            out = jnp.zeros((tokens, y.shape[0]), jnp.float32)
            for j in range(s.top_k):
                out += jnp.take(y_t, cols.assign[:, j], axis=0, mode="fill",
                                fill_value=0).astype(jnp.float32) * w[:, j, None]
            for e in range(s.n_shared):
                out = out.at[sl].add(jax.lax.dynamic_slice_in_dim(
                    y_t, cols.shared_start[e], sl.stop - sl.start, axis=0))
        return out.T.astype(y.dtype)

    # -- the request --------------------------------------------------------

    def _call(self, name, *args, **static):
        """Run program ``name``, compiled once per argument shapes."""
        key = (name, tuple(sorted(static.items())),
               tuple((a.shape, str(a.dtype)) for a in jax.tree.leaves(args)))
        exe = self._compiled.get(key)
        if exe is None:
            exe = self._jit[name].lower(*args, **static).compile()
            self._compiled[key] = exe
        return exe(*args)

    def programs(self) -> dict:
        """Module name -> HLO text of each compiled variant."""
        out = {}
        for (name, _, _), exe in self._compiled.items():
            out.setdefault(f"jit_{name}", []).append(exe.as_text())
        return out

    def survivors(self, lost: int) -> np.ndarray:
        return np.setdiff1d(np.arange(self.n_shards), [lost])

    def columns(self, counts: np.ndarray, tokens: int) -> int:
        """The buffer size for held-expert assignment counts ``counts``."""
        bn, s = self.tile(tokens), self.share
        n_sh = tokens // s.n_shares
        need = int(sum(-(-int(c) // bn) * bn for c in counts)) + (
            s.n_shared * (-(-n_sh // bn) * bn))
        return buffer_columns(need, bn)

    def __call__(self, params, x, lost: int):
        """This share's part of the layer, (d, T) in x's dtype, with shard
        ``lost`` lost in both products.  Also returns the chosen experts'
        ids (T, k), the coded gate/up products and the ``Columns``."""
        w, ids, counts, order = self._call("moe_route", params["router"],
                                           params["bias"], x)
        with tracing.span("moe.size"):
            host_counts = np.asarray(counts)
        columns = self.columns(host_counts, x.shape[1])
        lost_ = np.int32(lost)
        surv = self.survivors(lost)
        coded_gu, cols = self._call("moe_gate_up", x, ids, counts, order,
                                    params["w_gu"], lost_, columns=columns)
        gu = cm.recover(self.plan_gu, coded_gu, surv, product="gate_up")
        # The host would otherwise enqueue the whole request ahead of the
        # device, and every enqueued output holds its memory: waiting here
        # keeps one product's buffers live at a time (without it the cell
        # peaked at 15 GB of a 16 GB TPU v5e).
        gu.block_until_ready()
        coded_dn = self._call("moe_down", gu, params["w_down"], cols, lost_)
        y = cm.recover(self.plan_dn, coded_dn, surv, product="down")
        out = self._call("moe_combine", y, w, cols)
        return out, ids, coded_gu, cols
