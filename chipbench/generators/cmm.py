"""Generator of coded-matmul traffic: y = A x with one shard lost per
request, through ``coded_matmul.run`` (the fused Pallas kernel) and
``coded_matmul.recover``.

A closed loop with one caller, as an operator computing a layer's
projection waits for each product.  Request i multiplies layer i mod L of
the deployment's weights by the i mod P-th input of a pool made at set-up;
the lost shard of each request is drawn from the seed.  A request's time
runs from its issue to the recovered product being ready; the caller waits
for the shards' coded products (``coded_matmul`` span) and then recovers
(``recover`` span).  After the window a sample of requests drawn from the
seed, with the slowest in it, is compared with the plain reference
(``references/<config reference>.py``).

Traffic parameters (``traffic/<mix>.json``): ``chips``, ``n_shards``,
``overhead``, ``block_rows``, ``tokens`` (columns of x), ``x_pool``,
``check_requests``.
"""

from __future__ import annotations

import functools
import importlib
import time

import numpy as np

from .common import Checks, numpy_seed, root_key


def shapes(config: dict, traffic: dict) -> dict:
    """The product's sizes: the fused gate/up projection of one layer."""
    return dict(rows=2 * int(config["intermediate_size"]),
                k=int(config["hidden_size"]), n=int(traffic["tokens"]),
                bm=int(traffic["block_rows"]),
                layers=int(config["num_hidden_layers"]))


def make_layer(key, layer, rows, k, dtype):
    """Layer ``layer``'s weights from the run's weight key: N(0, 1/k)."""
    import jax
    import jax.numpy as jnp

    w = jax.random.normal(jax.random.fold_in(key, layer), (rows, k),
                          jnp.float32)
    return (w * (1.0 / np.sqrt(k))).astype(dtype)


def make_input(key, p, k, n, dtype):
    import jax
    import jax.numpy as jnp

    return jax.random.normal(jax.random.fold_in(key, p), (k, n),
                             jnp.float32).astype(dtype)


@functools.lru_cache(maxsize=None)
def _jitted(fn):
    import jax

    return jax.jit(fn, static_argnums=(2, 3, 4))


def _make_layer(*args):
    return _jitted(make_layer)(*args)


def _make_input(*args):
    return _jitted(make_input)(*args)


class Workload:
    def __init__(self, config, traffic, limits, seed, chips, spans):
        import jax
        import jax.numpy as jnp
        from jax.sharding import AxisType, Mesh, NamedSharding
        from jax.sharding import PartitionSpec as P

        from repro.core import coded_matmul as cm

        self.cm = cm
        self.config, self.traffic, self.limits = config, traffic, limits
        self.seed, self.spans, self.chips = seed, spans, chips
        self.shape = s = shapes(config, traffic)
        self.dtype = jnp.dtype(config["torch_dtype"])
        self.n_shards = int(traffic["n_shards"])
        self.plan = cm.plan_coded_matmul(
            rows=s["rows"], n_shards=self.n_shards,
            overhead=float(traffic["overhead"]), bm=s["bm"])
        self.devices = jax.devices()[:chips]
        if chips > 1:
            self.mesh = Mesh(np.array(self.devices).reshape(1, chips),
                             ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
            placed = NamedSharding(self.mesh, P())
        else:
            self.mesh = None
            placed = jax.sharding.SingleDeviceSharding(self.devices[0])
        interpret = self.devices[0].platform != "tpu"
        self.run_fn = jax.jit(functools.partial(
            cm.run, self.plan, mesh=self.mesh, use_pallas=True,
            interpret=interpret))
        root = root_key(seed)
        self.k_a, self.k_x = jax.random.fold_in(root, 1), jax.random.fold_in(
            root, 2)
        L, P_ = s["layers"], int(traffic["x_pool"])
        dt = self.dtype
        gen = jax.jit(lambda ka, kx: (
            tuple(make_layer(ka, l, s["rows"], s["k"], dt) for l in range(L)),
            tuple(make_input(kx, p, s["k"], s["n"], dt) for p in range(P_))),
            out_shardings=placed)
        self.a, self.x = gen(self.k_a, self.k_x)
        rng = numpy_seed(seed, 2)
        self.lost = rng.integers(0, self.n_shards, size=1 << 16)
        self.survivors = [np.setdiff1d(np.arange(self.n_shards), [s_])
                          for s_ in range(self.n_shards)]
        self.kept = {}    # -> (request, layer, pool index, out, y)
        self.latency = []

    def warm(self):
        """Compile the product at the cell's shapes and every recovery the
        traffic can ask for (one per lost shard)."""
        import jax

        out = jax.block_until_ready(self.run_fn(self.a[0], self.x[0]))
        for surv in self.survivors:
            jax.block_until_ready(self.cm.recover(self.plan, out, surv))

    def request(self, i: int):
        import jax

        L, P_ = len(self.a), len(self.x)
        with self.spans.span("coded_matmul"):
            out = jax.block_until_ready(
                self.run_fn(self.a[i % L], self.x[i % P_]))
        with self.spans.span("recover"):
            y = jax.block_until_ready(self.cm.recover(
                self.plan, out, self.survivors[self.lost[i % len(self.lost)]]))
        return out, y

    def window(self, seconds: float) -> dict:
        n_keep = int(self.traffic["check_requests"])
        rng = numpy_seed(self.seed, 3)
        slowest = (-1.0, None)
        t0 = time.perf_counter()
        i = 0
        while True:
            ti = time.perf_counter()
            out, y = self.request(i)
            lat = time.perf_counter() - ti
            self.latency.append(lat)
            item = (i % len(self.a), i % len(self.x), out, y)
            # Reservoir sample of the requests, drawn from the seed.
            slot = i if i < n_keep else int(rng.integers(0, i + 1))
            if slot < n_keep:
                self.kept[("sample", slot)] = (i,) + item
            if lat > slowest[0]:
                slowest = (lat, (i,) + item)
            i += 1
            if time.perf_counter() - t0 >= seconds:
                break
        elapsed = time.perf_counter() - t0
        self.kept[("slowest", 0)] = slowest[1]
        lat_ms = np.asarray(self.latency) * 1e3
        s = self.shape
        c_local = self.plan.code.n_coded // self.chips
        return dict(
            seconds=elapsed, attempted=i, failed=0,
            metrics={"products_per_s": i / elapsed,
                     "product_p95_ms": float(np.percentile(lat_ms, 95))},
            work={"products": i,
                  "product_flops": 2.0 * s["rows"] * s["k"] * s["n"],
                  "kernel_flops_per_chip": 2.0 * c_local * s["bm"] * s["k"]
                  * s["n"],
                  "kernel_bytes_per_chip": self.dtype.itemsize * (
                      s["rows"] * s["k"] + s["k"] * s["n"]
                      + c_local * s["bm"] * s["n"]),
                  "latency_ms_p50": float(np.percentile(lat_ms, 50)),
                  "latency_ms_top5": sorted(lat_ms.tolist())[-5:]})

    def release(self):
        """Drop the weights, inputs and compiled product; the sampled
        requests' outputs stay."""
        self.a = self.x = self.run_fn = None

    def _generator(self):
        plan = self.plan
        return plan.code.dense_generator()[plan.placement.reshape(-1)]

    def _inputs(self, layer, p):
        s = self.shape
        return (_make_layer(self.k_a, layer, s["rows"], s["k"], self.dtype),
                _make_input(self.k_x, p, s["k"], s["n"], self.dtype))

    def check(self, control: bool = False) -> Checks:
        """Worst gaps over the kept requests, relative to the largest
        reference entry: of the coded blocks' products, and of the
        recovered product.  ``control``: the reference computed in fp8
        (inputs and coded products) takes the kernel's place, and the
        program recovers from it."""
        import jax

        ref = importlib.import_module(
            f"chipbench.references.{self.config['reference']}")
        G, R = self._generator(), self.plan.code.R
        ck = Checks(self.limits)
        coded_gap = product_gap = 0.0
        with jax.default_device(self.devices[0]):
            for i, layer, p, out, y in self.kept.values():
                a, x = self._inputs(layer, p)
                y_ref = ref.product(a, x)
                c_ref = ref.coded(G, y_ref, R)
                if control:
                    out = ref.fp8(ref.coded(G, ref.product(ref.fp8(a),
                                                           ref.fp8(x)), R))
                    out = out.astype(self.dtype)
                    y = self.cm.recover(self.plan, out, self.survivors[
                        self.lost[i % len(self.lost)]])
                coded_gap = max(coded_gap, ref.gap(out, c_ref))
                product_gap = max(product_gap, ref.gap(y, y_ref))
                del a, x, y_ref, c_ref
        ck.add("coded_gap", coded_gap)
        ck.add("product_gap", product_gap)
        return ck

    def control(self) -> Checks:
        return self.check(control=True)
