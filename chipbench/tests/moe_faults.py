"""Faults planted under the coded expert layer's timed path, and a small-size
run of the ``moe`` cell that has them: the check has to come out not
correct.

  python -m chipbench.tests.moe_faults <fault>          # prints correct
  python -m chipbench.tests.moe_faults <fault> --full   # the cell's size,
                                                        # on a TPU
"""

from __future__ import annotations

import contextlib
import dataclasses
import json
import sys
import time

WORKLOAD = "moe.moonlight_moe.skewed_lost_shard"
SMALL = {"config": {"hidden_size": 256, "moe_intermediate_size": 128,
                    "num_hidden_layers": 4},
         "traffic": {"tokens": 256, "shared_tokens": 32,
                     "block_rows_gate_up": 32, "block_rows_down": 32,
                     "x_pool": 2, "check_requests": 3}}


@contextlib.contextmanager
def planted(fault: str):
    """Plant ``fault`` in the program for the duration of the block."""
    import jax

    from repro.core import coded_moe as CM

    route = CM.route
    saved = [(CM, "route", route)]
    if fault == "moe_softmax":
        # The router scores with a softmax in place of DeepSeek-V3's sigmoid.
        CM.route = lambda logits, k, **kw: route(
            logits, k, **dict(kw, scoring="softmax"))
    elif fault == "moe_scale_dropped":
        CM.route = lambda logits, k, **kw: route(logits, k,
                                                 **dict(kw, scale=1.0))
    elif fault == "moe_no_shared":
        # The combine leaves the shared experts' columns out.
        combine = CM.CodedExperts.moe_combine
        saved.append((CM.CodedExperts, "moe_combine", combine))

        def routed_only(self, y, w, cols):
            share = self.share
            self.share = dataclasses.replace(share, n_shared=0)
            try:
                return combine(self, y, w, cols)
            finally:
                self.share = share
        CM.CodedExperts.moe_combine = routed_only
    elif fault == "moe_wrong_expert":
        # Each column tile is multiplied by the next group's expert.
        dispatch = CM.CodedExperts._dispatch
        saved.append((CM.CodedExperts, "_dispatch", dispatch))

        def shifted(self, *args):
            cols = dispatch(self, *args)
            groups = self.share.held + self.share.n_shared
            return cols._replace(tile_group=(cols.tile_group + 1) % groups)
        CM.CodedExperts._dispatch = shifted
    elif fault == "moe_lost_shard_used":
        # The collector decodes from every shard, the lost one's blocks too.
        saved.append((CM.CodedExperts, "survivors",
                      CM.CodedExperts.survivors))
        CM.CodedExperts.survivors = lambda self, lost: list(
            range(self.n_shards))
    else:
        raise ValueError(f"unknown fault {fault!r}")
    jax.clear_caches()
    try:
        yield
    finally:
        for obj, name, old in reversed(saved):
            setattr(obj, name, old)
        jax.clear_caches()


def run_small(fault: str = None, seed: int = 2 ** 31 + 11,
              seconds: float = 0.5, full: bool = False) -> dict:
    """A run of the cell with ``fault``: at a small size on any device, or
    (``full``) at the cell's own size on a TPU."""
    from chipbench import run as R

    ctx = planted(fault) if fault else contextlib.nullcontext()
    with ctx:
        return R.run(R.load_benchmark(), WORKLOAD, seed, seconds, False,
                     require_tpu=full, t_start=time.perf_counter(),
                     overrides=None if full else SMALL)


if __name__ == "__main__":
    fault = sys.argv[1] if len(sys.argv) > 1 and sys.argv[1] != "none" else None
    full = "--full" in sys.argv[2:]
    out = run_small(fault, seconds=2.0 if full else 0.5, full=full)
    print(json.dumps({"correct": out["correct"], "checks": out["checks"]}))
