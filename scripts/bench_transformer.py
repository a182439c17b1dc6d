#!/usr/bin/env python
"""Production-shape transformer benchmark on the real chip (VERDICT r2 #4).

Runs the claimed beyond-parity model paths at REAL shapes — BERT-base
(L12/d768/h12/ff3072, seq 512, 30522 vocab), Switch-MoE at capacity
pressure, and the GPipe PipelineLM with realistic microbatches — on
whatever jax.devices() provides (single-chip mesh: correctness of the
multi-axis shardings is pytest/dryrun-proven on the virtual mesh; this
measures that the shapes COMPILE, FIT and RUN at speed on hardware,
surfacing any VMEM/layout traps toy shapes hide).

Steps are dispatched as lax.scan chunks (BERT.fit_chunked) so per-step
host sync latency stays out of the step time: per-chunk arrival
timestamps are printed as audit evidence, bench.py-style.

One JSON line per model.  ``BENCH_T_MODELS=bert,moe,pipeline`` selects.
"""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np  # noqa: E402

from bench import peak_bf16  # noqa: E402 — one device_kind→peak table repo-wide


def _mfu(flops_per_sec, device):
    peak = peak_bf16(device)
    return round(flops_per_sec / peak, 4) if peak else None


def _mem_stats(dev):
    try:
        s = dev.memory_stats() or {}
        peak, used = s.get("peak_bytes_in_use"), s.get("bytes_in_use")
        # backends without allocator stats return empty/zero — null, not 0.0
        return {"hbm_peak_mb": round(peak / 1e6, 1) if peak else None,
                "hbm_in_use_mb": round(used / 1e6, 1) if used else None}
    except Exception:  # noqa: BLE001 — not all platforms expose stats
        return {"hbm_peak_mb": None, "hbm_in_use_mb": None}


def bench_bert(devs, steps, chunk):
    import jax
    from dmlc_core_tpu.models.bert import BERT
    from dmlc_core_tpu.parallel.mesh import MeshSpec, create_mesh

    B, S = int(os.environ.get("BENCH_T_BATCH", 8)), 512
    mesh = create_mesh(MeshSpec(data=1), devices=devs[:1])
    model = BERT(mesh=mesh)           # BERT-base defaults
    model.init_params(0)
    n_params = sum(int(np.prod(v.shape)) for v in model.params.values())
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, model.param.vocab_size, size=(B, S))
    loss, secs, chunk_times = model.fit_chunked(
        tokens, tokens.copy(), np.ones((B, S), np.float32),
        n_steps=steps, chunk=chunk)
    flops = 6 * n_params * B * S      # fwd+bwd matmul estimate
    return {
        "model": "bert_base", "layers": 12, "d_model": 768, "seq": S,
        "batch": B, "params_m": round(n_params / 1e6, 1),
        "steps": steps, "seconds": round(secs, 3),
        "steps_per_sec": round(steps / secs, 3),
        "tokens_per_sec": round(B * S * steps / secs),
        "approx_mfu": _mfu(flops * steps / secs, devs[0]),
        "final_loss": round(loss, 4),
        "chunk_times": [(d, round(t, 3)) for d, t in chunk_times],
        **_mem_stats(devs[0]),
    }


def bench_moe(devs, steps, chunk):
    import jax
    from dmlc_core_tpu.models.bert import BERT
    from dmlc_core_tpu.parallel.mesh import MeshSpec, create_mesh

    # capacity-pressure config: tokens/expert ≈ capacity at cf=1.0, so
    # dispatch overflow/padding paths are genuinely exercised
    B, S = int(os.environ.get("BENCH_T_BATCH", 8)), 512
    mesh = create_mesh(MeshSpec(data=1), devices=devs[:1])
    model = BERT(mesh=mesh, n_layers=6, d_model=512, n_heads=8, d_ff=2048,
                 ffn_type="moe", n_experts=8, capacity_factor=1.0)
    model.init_params(0)
    n_params = sum(int(np.prod(v.shape)) for v in model.params.values())
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, model.param.vocab_size, size=(B, S))
    loss, secs, chunk_times = model.fit_chunked(
        tokens, tokens.copy(), np.ones((B, S), np.float32),
        n_steps=steps, chunk=chunk)
    return {
        "model": "switch_moe", "layers": 6, "d_model": 512, "seq": S,
        "batch": B, "experts": 8, "capacity_factor": 1.0,
        "params_m": round(n_params / 1e6, 1),
        "steps": steps, "seconds": round(secs, 3),
        "steps_per_sec": round(steps / secs, 3),
        "tokens_per_sec": round(B * S * steps / secs),
        "final_loss": round(loss, 4),
        "chunk_times": [(d, round(t, 3)) for d, t in chunk_times],
        **_mem_stats(devs[0]),
    }


def bench_pipeline(devs, steps, chunk):
    import jax
    from jax.sharding import Mesh
    from dmlc_core_tpu.parallel.pipeline import PipelineLM

    # realistic microbatching: 8 microbatches through the GPipe scan
    # schedule (pp=1 on a single chip — the schedule, buffers and
    # collective-permute program still run)
    B, S = 16, 512
    mesh = Mesh(np.asarray(devs[:1]).reshape(1, 1), ("data", "pipe"))
    model = PipelineLM(mesh=mesh, n_layers=12, d_model=512, n_heads=8,
                      d_ff=2048, vocab_size=30522, max_len=S, n_micro=8)
    model.init_params(0)
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 30522, size=(B, S)).astype(np.int32)
    mask = np.ones((B, S), np.float32)
    loss, secs, chunk_times = model.fit_chunked(
        tokens, tokens.copy(), mask, n_steps=steps, chunk=chunk)
    return {
        "model": "pipeline_lm", "layers": 12, "d_model": 512, "seq": S,
        "batch": B, "n_micro": 8,
        "steps": steps, "seconds": round(secs, 3),
        "steps_per_sec": round(steps / secs, 3),
        "tokens_per_sec": round(B * S * steps / secs),
        "final_loss": round(float(loss), 4),
        "chunk_times": [(d, round(t, 3)) for d, t in chunk_times],
        **_mem_stats(devs[0]),
    }


def main() -> None:
    import jax

    steps = int(os.environ.get("BENCH_T_STEPS", 30))
    chunk = int(os.environ.get("BENCH_T_CHUNK", 10))
    models = os.environ.get("BENCH_T_MODELS", "bert,moe,pipeline").split(",")
    devs = jax.devices()
    fns = {"bert": bench_bert, "moe": bench_moe, "pipeline": bench_pipeline}
    for name in models:
        try:
            out = fns[name.strip()](devs, steps, chunk)
        except Exception as e:  # noqa: BLE001 — report traps, keep going
            out = {"model": name.strip(),
                   "error": f"{type(e).__name__}: {e}"[:600]}
        out["platform"] = devs[0].platform
        print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main()
