"""Generator of coded expert-layer traffic: one chip's share of a
mixture-of-experts layer (``core/coded_moe.CodedExperts``) with one shard
lost per request in both of its coded products.

A closed loop with one caller, as the ``cmm`` cells.  Request i runs MoE
layer 1 + (i mod L) of the deployment's weights (L MoE layers held) on
input (i div L) mod P of a pool made at set-up, so every (layer, input)
pair comes round every L P requests; the lost shard of each request is
drawn from the seed.  A request's time runs from its issue to the
layer's output being ready (``layer`` span): routing, the one readback of
the held experts' counts, both coded products and their recoveries, and
the combine.  Set-up routes every (layer, input) pair of the pool, and
compiles each column buffer size they need with every lost shard.  After
the window a sample of requests drawn from the seed, with the slowest in
it, is compared with the plain reference (``references/<config
reference>.py``).

Inputs are skewed by topic: ``topics`` centroids N(0, I), each token's
topic drawn Zipf(``topic_zipf``) over them, x = (centroid + N(0, I)) /
sqrt(2).  The router, its bias and the centroids come from
``ROUTING_SEED``; the expert weights, each input's tokens and the lost
shards from the run's seed.

Traffic parameters (``traffic/<mix>.json``): ``chips``, ``tokens`` (routed
per request, the 8 chips' batch), ``shared_tokens`` (this chip's slice),
``n_shards``, ``overhead``, ``block_rows_gate_up``, ``block_rows_down``,
``x_pool``, ``topics``, ``topic_zipf``, ``check_requests``.
"""

from __future__ import annotations

import functools
import importlib
import sys
import time

import numpy as np

from .common import Checks, numpy_seed, root_key

BIAS_STD = 0.02     # the assumed e_score_correction_bias scale
EXPERTS = ("gate", "up", "down", "shared_gate", "shared_up", "shared_down")
CODED_STRIDE = 16   # every 16th column of a kept request's coded gate/up
# The router, its bias and the topic centroids stand for one trained router
# and one deployment's domains: drawn from this fixed seed, every run routes
# the same mix (drawn from the run's seed, the mean routed load moved ~3%
# from seed to seed in a numpy replay of the routing).
ROUTING_SEED = 0


def share_of(config: dict):
    """The ``ExpertShare`` the configuration describes; raises on a
    configuration whose router or experts this layer does not compute."""
    from repro.core.coded_moe import ExpertShare

    want = dict(topk_method="noaux_tc", n_group=1, topk_group=1,
                norm_topk_prob=True, hidden_act="silu", scoring_func="sigmoid")
    off = {k: config.get(k) for k, v in want.items() if config.get(k) != v}
    n_experts = int(config["published"]["n_routed_experts"])
    n_shares = int(config["expert_parallel"])
    if off or n_experts != n_shares * int(config["n_routed_experts"]):
        raise ValueError(f"not a DeepSeek-V3 MoE layer held 1/{n_shares}: "
                         f"{off or config['n_routed_experts']}")
    return ExpertShare(
        d_model=int(config["hidden_size"]),
        d_expert=int(config["moe_intermediate_size"]),
        n_experts=n_experts, top_k=int(config["num_experts_per_tok"]),
        n_shared=int(config["n_shared_experts"]),
        share=int(config["expert_share"]), n_shares=n_shares,
        scoring=config["scoring_func"],
        scale=float(config["routed_scaling_factor"]))


def make_layer(key, route_key, layer, share, dtype):
    """MoE layer ``layer``'s weights, in the published layout: from the
    configuration's routing key, the router and its bias over every expert
    in float32; from the run's weight key and in ``dtype``, the held
    experts' gate/up (held, f, d) and down (held, d, f) (expert e drawn
    from its global id) and the shared experts as one SwiGLU."""
    import jax
    import jax.numpy as jnp

    d, f, s = share.d_model, share.d_expert, share
    normal = lambda k, shape, fan: jax.random.normal(
        k, shape, jnp.float32) * (1.0 / np.sqrt(fan))
    k_router, k_bias = jax.random.split(jax.random.fold_in(route_key, layer))
    ks = jax.random.split(jax.random.fold_in(key, layer), 5)

    def expert(e):
        ke = jax.random.split(jax.random.fold_in(ks[4], e), 3)
        return (normal(ke[0], (f, d), d), normal(ke[1], (f, d), d),
                normal(ke[2], (d, f), f))

    gate, up, down = jax.vmap(expert)(jnp.arange(s.first, s.first + s.held))
    fs = s.n_shared * f
    experts = dict(
        gate=gate, up=up, down=down,
        shared_gate=normal(jax.random.fold_in(ks[2], 0), (fs, d), d),
        shared_up=normal(jax.random.fold_in(ks[2], 1), (fs, d), d),
        shared_down=normal(ks[3], (d, fs), fs))
    return dict({k: v.astype(dtype) for k, v in experts.items()},
                router=normal(k_router, (s.n_experts, d), d),
                bias=BIAS_STD * jax.random.normal(k_bias, (s.n_experts,),
                                                  jnp.float32))


def make_input(key, route_key, p, d, tokens, topics, zipf, dtype):
    """Input ``p`` of the pool, (d, tokens): topic-skewed tokens around the
    configuration's centroids (routing key), drawn from the run's key."""
    import jax
    import jax.numpy as jnp

    centroids = jax.random.normal(route_key, (topics, d))
    k_topic, k_noise = jax.random.split(jax.random.fold_in(key, p))
    logits = -zipf * jnp.log(jnp.arange(1, topics + 1, dtype=jnp.float32))
    topic = jax.random.categorical(k_topic, logits, shape=(tokens,))
    x = (centroids[topic] + jax.random.normal(k_noise, (tokens, d))) / np.sqrt(
        2.0)
    return x.T.astype(dtype)


class Workload:
    def __init__(self, config, traffic, limits, seed, chips, spans):
        import jax
        import jax.numpy as jnp

        from repro.core import coded_moe

        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.spans, self.chips = seed, spans, chips
        self.share = share = share_of(config)
        self.dtype = jnp.dtype(config["torch_dtype"])
        self.tokens = int(traffic["tokens"])
        if int(traffic["shared_tokens"]) != self.tokens // share.n_shares:
            raise ValueError("shared_tokens is this chip's slice: "
                             f"{self.tokens} / {share.n_shares}")
        self.n_shards = int(traffic["n_shards"])
        self.devices = jax.devices()[:1]
        self.layer = coded_moe.CodedExperts(
            share, n_shards=self.n_shards, overhead=float(traffic["overhead"]),
            bm_gate_up=int(traffic["block_rows_gate_up"]),
            bm_down=int(traffic["block_rows_down"]), use_pallas=True,
            interpret=self.devices[0].platform != "tpu")
        root = root_key(seed)
        self.k_w, self.k_x = (jax.random.fold_in(root, 1),
                              jax.random.fold_in(root, 2))
        route = root_key(ROUTING_SEED)
        self.k_r, self.k_c = (jax.random.fold_in(route, 1),
                              jax.random.fold_in(route, 2))
        first = int(config["first_k_dense_replace"])
        self.layer_ids = list(range(first, int(config["num_hidden_layers"])))
        dt = self.dtype

        @jax.jit
        def packed(k, l):
            w = _make_layer(k, self.k_r, l, share, dt)
            w_gu, w_down = coded_moe.pack(*(w[n] for n in EXPERTS))
            return dict(router=w["router"], bias=w["bias"], w_gu=w_gu,
                        w_down=w_down)

        self.params = [packed(self.k_w, l) for l in self.layer_ids]
        self.x = [_make_input(self.k_x, self.k_c, p, share.d_model,
                              self.tokens,
                              int(traffic["topics"]),
                              float(traffic["topic_zipf"]), dt)
                  for p in range(int(traffic["x_pool"]))]
        rng = numpy_seed(seed, 2)
        self.lost = rng.integers(0, self.n_shards, size=1 << 16)
        self.kept = {}
        self.latency = []
        self.stats = []     # each request's (routed, shared, live) columns
        self.buffers = []   # each request's column buffer

    def _pair(self, i):
        n = len(self.params)
        return i % n, (i // n) % len(self.x)

    def _sample(self, coded, cols):
        """What the check keeps of a request's coded gate/up product: every
        ``CODED_STRIDE``-th column, and the buffer's width."""
        return coded[:, ::CODED_STRIDE], cols.tok.shape[0]

    def warm(self):
        """Route every (layer, input) pair of the pool, then run each column
        buffer size they need with every lost shard: every program and
        every recovery the window can ask for."""
        import jax

        need = {}
        hot = []
        for li, params in enumerate(self.params):
            for p, x in enumerate(self.x):
                counts = self.layer._call("moe_route", params["router"],
                                          params["bias"], x)[2]
                counts = np.asarray(counts)
                hot.append(counts.max() / max(counts.mean(), 1e-9))
                need.setdefault(self.layer.columns(counts, self.tokens),
                                (li, p))
        print(f"chipbench: moe: column buffers {sorted(need)}; hottest held "
              f"expert's load over the mean: mean {np.mean(hot):.4f}, max "
              f"{np.max(hot):.4f} over {len(hot)} (layer, input) pairs",
              file=sys.stderr, flush=True)
        for li, p in need.values():
            for lost in range(self.n_shards):
                out, ids, coded, cols = self.layer(self.params[li], self.x[p],
                                                   lost)
                jax.block_until_ready((out, self._sample(coded, cols)[0]))

    def request(self, i: int):
        import jax

        li, p = self._pair(i)
        with self.spans.span("layer"):
            res = self.layer(self.params[li], self.x[p],
                             int(self.lost[i % len(self.lost)]))
            jax.block_until_ready(res[0])
        return res

    def window(self, seconds: float) -> dict:
        n_keep = int(self.traffic["check_requests"])
        rng = numpy_seed(self.seed, 3)
        slowest = (-1.0, None)
        t0 = time.perf_counter()
        i = 0
        while True:
            ti = time.perf_counter()
            out, ids, coded, cols = self.request(i)
            lat = time.perf_counter() - ti
            self.latency.append(lat)
            self.stats.append(cols.stats)
            self.buffers.append(cols.tok.shape[0])
            slot = i if i < n_keep else int(rng.integers(0, i + 1))
            if slot < n_keep or lat > slowest[0]:
                item = (i,) + self._pair(i) + (out, ids) + self._sample(
                    coded, cols)
                if slot < n_keep:
                    self.kept[("sample", slot)] = item
                if lat > slowest[0]:
                    slowest = (lat, item)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.kept[("slowest", 0)] = slowest[1]
        stats = np.asarray([np.asarray(s) for s in self.stats])
        routed, shared, _ = stats.T
        lat_ms = np.asarray(self.latency) * 1e3
        s, lay = self.share, self.layer
        products = []
        for plan, rows, k in ((lay.plan_gu, 2 * s.d_expert, s.d_model),
                              (lay.plan_dn, s.d_model, s.d_expert)):
            products.append(dict(C=int(plan.code.n_coded), bm=int(plan.bm),
                                 rows=rows, k=k, E=s.held + s.n_shared,
                                 itemsize=self.dtype.itemsize))
        return dict(
            seconds=elapsed, attempted=i, failed=0,
            metrics={"products_per_s": i / elapsed,
                     "product_p95_ms": float(np.percentile(lat_ms, 95))},
            work={"products": i,
                  "real_columns": (routed + shared).tolist(),
                  "buffer_columns": self.buffers,
                  "flops_per_column": 2.0 * 3 * s.d_model * s.d_expert,
                  "kernel_products": products,
                  "latency_ms_p50": float(np.percentile(lat_ms, 50)),
                  "latency_ms_top5": sorted(lat_ms.tolist())[-5:]},
            programs=self.layer.programs())

    def release(self):
        """Drop the weights, inputs and compiled programs; the sampled
        requests' outputs stay."""
        self.params = self.x = None
        self.layer._compiled.clear()

    def check(self, control: bool = False) -> Checks:
        """Worst readings over the kept requests, against the reference:
        ``route_flip_share``, the share of tokens whose chosen experts
        differ; ``layer_gap``, the largest |got - ref| over the largest
        |ref| on the layer's output, flipped tokens left out; ``coded_gap``,
        the same on the kept columns of the coded gate/up products against
        G (W x) of each column's token and expert, the lost shard's rows
        left out.  The reference lays out the columns itself, from its own
        chosen experts (the program's for the flipped tokens only).
        ``control``: the reference with the experts' operands and the
        coded products rounded to fp8 takes the program's place.  Each
        comparison is one compiled program per shape."""
        import jax
        import jax.numpy as jnp

        ref = importlib.import_module(
            f"chipbench.references.{self.config['reference']}")
        s, plan = self.share, self.layer.plan_gu
        bn = self.layer.tile(self.tokens)
        G = plan.code.dense_generator()[plan.placement.reshape(-1)]
        R, rows = plan.code.R, plan.blocks_per_shard * plan.bm
        f = s.d_expert
        kw = dict(first=s.first, top_k=s.top_k, scale=s.scale,
                  shared_tokens=s.shared_slice(self.tokens))
        layer = jax.jit(functools.partial(ref.layer, **kw))
        fp8_layer = jax.jit(functools.partial(ref.layer, **kw, cast=ref.fp8))

        @functools.partial(jax.jit, static_argnums=4)
        def coded(a, x, tok, group, cast):
            """G (W x) of the kept columns, operands and result rounded by
            ``cast`` (the control's fp8) where given."""
            cast = cast or (lambda v: v)
            c = ref.coded(G, ref.expert_columns(cast(a), cast(x), tok, group),
                          R)
            return cast(c)

        ck = Checks(self.limits)
        flip = layer_gap = coded_gap = 0.0
        with jax.default_device(self.devices[0]):
            for i, li, p, out, ids, got, width in self.kept.values():
                w = _make_layer(self.k_w, self.k_r, self.layer_ids[li], s,
                                self.dtype)
                x = _make_input(self.k_x, self.k_c, p, s.d_model, self.tokens,
                                int(self.traffic["topics"]),
                                float(self.traffic["topic_zipf"]), self.dtype)
                args = (x, w["router"], w["bias"]) + tuple(
                    w[n] for n in EXPERTS)
                y_ref, ids_ref = layer(*args)
                if control:
                    out, ids = fp8_layer(*args)
                moved = ref.flipped(jnp.asarray(ids), ids_ref)
                flip = max(flip, float(moved.mean()))
                layer_gap = max(layer_gap, ref.gap(out, y_ref, cols=~moved))
                # Each kept column's expert, as [gate; up] rows: the held
                # experts in order, then the shared experts' halves.
                halves = [jnp.concatenate([w["shared_gate"][e * f:(e + 1) * f],
                                           w["shared_up"][e * f:(e + 1) * f]])
                          for e in range(s.n_shared)]
                a = jnp.concatenate([jnp.concatenate([w["gate"], w["up"]], 1),
                                     jnp.stack(halves)])
                chosen = jnp.where(moved[:, None], jnp.asarray(ids), ids_ref)
                tok, group, need = ref.columns(
                    chosen, first=s.first, held=s.held, n_shared=s.n_shared,
                    shared_tokens=kw["shared_tokens"], tile=bn, width=width)
                if need > width:    # the buffer dropped columns
                    coded_gap = float("inf")
                tok, group = tok[::CODED_STRIDE], group[::CODED_STRIDE]
                want = coded(a, x, jnp.asarray(tok), jnp.asarray(group),
                             None)
                if control:
                    got = coded(a, x, tok, group, ref.fp8)
                lost = int(self.lost[i % len(self.lost)])
                survive = np.ones(want.shape[0], bool)
                survive[lost * rows:(lost + 1) * rows] = False
                coded_gap = max(coded_gap, ref.gap(got, want, rows=survive))
                del w, x, y_ref, want
        ck.add("coded_gap", coded_gap)
        ck.add("layer_gap", layer_gap)
        ck.add("route_flip_share", flip)
        return ck

    def control(self) -> Checks:
        return self.check(control=True)


@functools.lru_cache(maxsize=None)
def _jitted(fn, static):
    import jax

    return jax.jit(fn, static_argnums=static)


def _make_layer(*args):
    return _jitted(make_layer, (3, 4))(*args)


def _make_input(*args):
    return _jitted(make_input, (3, 4, 5, 6, 7))(*args)
