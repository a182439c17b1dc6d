#!/usr/bin/env python
"""Config-4 proxy bench: KVStore dist_sync on a BERT-base-shaped grad set.

Measures the effect of gradient-fusion bucketing (parallel/kvstore.py):
one step = push all keys, pull all keys (allreduce + SGD update).  The
per-key mode is simulated with bucket_bytes=1 (every key its own
collective) — what the store did before bucketing.

Run on the 8-device CPU mesh (the multi-worker proxy BASELINE.md config 4
prescribes for CI):

    XLA_FLAGS=--xla_force_host_platform_device_count=8 JAX_PLATFORMS=cpu \
        python scripts/bench_kvstore.py

Prints one JSON line per mode with collective count and steps/s.
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402


def bert_base_shapes(layers: int = 12, hidden: int = 768, vocab: int = 30522):
    """The BERT-base parameter inventory (~110M params, ~200 tensors)."""
    shapes = [("embed.word", (vocab, hidden)),
              ("embed.pos", (512, hidden)),
              ("embed.type", (2, hidden)),
              ("embed.ln.g", (hidden,)), ("embed.ln.b", (hidden,))]
    for i in range(layers):
        p = f"l{i}."
        shapes += [
            (p + "q.w", (hidden, hidden)), (p + "q.b", (hidden,)),
            (p + "k.w", (hidden, hidden)), (p + "k.b", (hidden,)),
            (p + "v.w", (hidden, hidden)), (p + "v.b", (hidden,)),
            (p + "o.w", (hidden, hidden)), (p + "o.b", (hidden,)),
            (p + "ln1.g", (hidden,)), (p + "ln1.b", (hidden,)),
            (p + "ffn1.w", (hidden, 4 * hidden)), (p + "ffn1.b", (4 * hidden,)),
            (p + "ffn2.w", (4 * hidden, hidden)), (p + "ffn2.b", (hidden,)),
            (p + "ln2.g", (hidden,)), (p + "ln2.b", (hidden,)),
        ]
    shapes += [("pool.w", (hidden, hidden)), ("pool.b", (hidden,))]
    return shapes


def main() -> None:
    # an N-device CPU mesh (the same hook tests/conftest.py uses)
    ndev = int(os.environ.get("BENCH_KV_DEVICES", 8))
    if ndev > 1:
        from dmlc_core_tpu.utils import force_cpu_devices
        force_cpu_devices(ndev)
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from dmlc_core_tpu.parallel.kvstore import KVStore
    from dmlc_core_tpu.parallel.mesh import local_mesh

    steps = int(os.environ.get("BENCH_KV_STEPS", 5))
    mesh = local_mesh()
    W = mesh.devices.size
    sharding1 = NamedSharding(mesh, P("data"))
    # full BERT-base hidden=768 (110M params) on real chips; the CI proxy
    # shrinks hidden/vocab (the contrast under test is collective COUNT,
    # which depends only on the 199-key structure, not tensor width —
    # 8 virtual CPU devices on one core can't move 437MB/step)
    hidden = int(os.environ.get("BENCH_KV_HIDDEN", 128))
    vocab = int(os.environ.get("BENCH_KV_VOCAB", 4000))
    shapes = bert_base_shapes(hidden=hidden, vocab=vocab)
    n_params = sum(int(np.prod(s)) for _, s in shapes)
    rng = np.random.default_rng(0)
    grads = {k: jax.device_put(
        rng.normal(size=(W, *s)).astype(np.float32) / W, sharding1)
        for k, s in shapes}

    # BASELINE config 4's target line is BUS BANDWIDTH: for a ring-style
    # allreduce of S bytes over n workers every worker moves
    # 2·(n-1)/n · S bytes over its links (the NCCL busbw convention), so
    # achieved bus GB/s = that / sync seconds.  Meaningless at W=1 (the
    # psum is a no-op) → null.
    bus_bytes = 2 * (W - 1) / W * n_params * 4

    for label, bucket_bytes in (("per-key", 1), ("bucketed", 64 << 20)):
        kv = KVStore.create("dist_sync", mesh=mesh, learning_rate=0.01,
                            bucket_bytes=bucket_bytes)
        kv.init([k for k, _ in shapes],
                [np.zeros(s, np.float32) for _, s in shapes])
        # warm the jit caches
        kv.push([k for k, _ in shapes], [grads[k] for k, _ in shapes])
        kv.pull([k for k, _ in shapes])

        # sync-only timing (the collective itself, no SGD update): the
        # number the bus-bandwidth target compares against
        flat_grads = {k: grads[k] for k, _ in shapes}
        sync_out = kv._sync_bucketed(dict(flat_grads))     # warm
        jax.block_until_ready(list(sync_out.values()))
        t0 = time.perf_counter()
        for _ in range(steps):
            sync_out = kv._sync_bucketed(dict(flat_grads))
        jax.block_until_ready(list(sync_out.values()))
        dt_sync = (time.perf_counter() - t0) / steps

        kv.stats = {"sync_calls": 0, "keys_synced": 0}
        t0 = time.perf_counter()
        for _ in range(steps):
            kv.push([k for k, _ in shapes], [grads[k] for k, _ in shapes])
            out = kv.pull([k for k, _ in shapes])
        jax.block_until_ready(out)
        dt = time.perf_counter() - t0
        print(json.dumps({
            "mode": label,
            "keys": len(shapes),
            "params": n_params,
            "workers": W,
            "collectives_per_step": kv.stats["sync_calls"] // steps,
            "steps_per_sec": round(steps / dt, 3),
            "grad_mb_per_step": round(n_params * 4 / 1e6, 1),
            "sync_ms": round(dt_sync * 1e3, 2),
            "allreduce_bus_mb_per_step": round(bus_bytes / 1e6, 1),
            "bus_gbps": (round(bus_bytes / dt_sync / 1e9, 3)
                         if W > 1 else None),
            "bus_gbps_incl_update": (round(bus_bytes * steps / dt / 1e9, 3)
                                     if W > 1 else None),
            "platform": jax.devices()[0].platform,
        }))


if __name__ == "__main__":
    main()
