"""Benchmark: training-step performance at the reference's SceneFlow config.

BASELINE.md config 4 (reference: train_stereo.py:221-227, README.md:106-110):
batch 8, crop 320x720, 22 GRU iterations, mixed precision — the configuration
the reference trains its published models with on 2x RTX 6000.  Measures on
one TPU chip:

* step time via the chained-differencing protocol (see bench.py: K steps run
  on-device inside ``lax.fori_loop``, two chain lengths differenced to cancel
  dispatch overhead);
* compiled FLOPs per step from XLA cost analysis -> achieved TFLOP/s and MFU
  against the chip's bf16 peak;
* peak HBM from device memory stats (when the runtime reports them);
* optionally (--trace) a profiler trace of one jitted step
  (``benchmark/trace_reduce.py`` reduces such a trace to numbers).

Prints ONE JSON line compatible with bench.py's contract.  ``vs_baseline``
compares against the reference's published training protocol the only way
available offline: 200k steps over ~1 week of 2x RTX 6000 time (the README's
training recipe) -> ~0.33 steps/s assumed for the pair; see BASELINE.md for
why no measured GPU number exists.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

# The reference README's recipe: "about 1 week on 2 RTX 6000" for 200k steps
# (README.md:106-110) -> 200000 / (7*86400) ~= 0.33 steps/s on the GPU pair.
# External inference like the 26-FPS figure in bench.py; re-measure when GPUs
# are reachable.
BASELINE_STEPS_PER_S = 200_000 / (7 * 86_400)


BATCH, H, W, ITERS = 8, 320, 720, 22
K_LO, K_HI = 1, 4
REPEATS = 3


def make_batch(rng: np.random.Generator):
    disp = rng.uniform(1.0, 40.0, (BATCH, H, W)).astype(np.float32)
    return {
        "image1": jnp.asarray(rng.uniform(0, 255, (BATCH, H, W, 3)),
                              jnp.float32),
        "image2": jnp.asarray(rng.uniform(0, 255, (BATCH, H, W, 3)),
                              jnp.float32),
        "flow": jnp.asarray(-disp),
        "valid": jnp.ones((BATCH, H, W), jnp.float32),
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--trace", default=None,
                    help="capture a profiler trace into this directory")
    ap.add_argument("--corr_backend", default=None,
                    help="override the default correlation backend")
    ap.add_argument("--remat_save", nargs="*", default=None,
                    help="remat policy save names (config.remat_save)")
    args = ap.parse_args()

    from raft_stereo_tpu.config import RaftStereoConfig, TrainConfig
    from raft_stereo_tpu.profiling import (chained_seconds_per_call,
                                           device_memory_stats, trace)
    from raft_stereo_tpu.telemetry.costs import peak_flops_for
    from raft_stereo_tpu.telemetry.events import bench_record
    from raft_stereo_tpu.training.state import create_train_state
    from raft_stereo_tpu.training.step import train_step

    # Persistent compilation cache: the step compiles in O(minutes); repeat
    # bench/trace runs should not pay it again.
    from raft_stereo_tpu.profiling import setup_compilation_cache
    setup_compilation_cache()

    model_kw = {"mixed_precision": True}
    if args.corr_backend:
        model_kw["corr_backend"] = args.corr_backend
    if args.remat_save is not None:
        model_kw["remat_save"] = tuple(args.remat_save)
    model_cfg = RaftStereoConfig(**model_kw)
    train_cfg = TrainConfig(batch_size=BATCH, train_iters=ITERS,
                            image_size=(H, W))

    state = create_train_state(model_cfg, train_cfg, jax.random.PRNGKey(0),
                               image_shape=(1, H, W, 3))
    batch = make_batch(np.random.default_rng(0))
    step = functools.partial(train_step, iters=ITERS,
                             loss_gamma=train_cfg.loss_gamma,
                             max_flow=train_cfg.max_flow)

    if args.trace:
        # Trace-only mode: one warm + one traced step through the plain
        # jitted step (reduce with benchmark/trace_reduce.py).
        jitted = jax.jit(step, donate_argnums=())
        _, m = jitted(state, batch)
        float(m["loss"])
        with trace(args.trace):
            _, m = jitted(state, batch)
            float(m["loss"])
        print(json.dumps({"trace": args.trace}))
        return

    # FLOPs of ONE compiled step from XLA's cost model (the basis for MFU).
    compiled = jax.jit(step, donate_argnums=()).lower(state, batch).compile()
    cost = compiled.cost_analysis() or {}
    flops_per_step = float(cost.get("flops", 0.0))

    @functools.partial(jax.jit, static_argnums=(2,))
    def chain(state0, batch, k):
        def body(i, s):
            b = dict(batch, image1=batch["image1"] + i * 1e-6)
            s2, _ = step(s, b)
            return s2
        s = jax.lax.fori_loop(0, k, body, state0)
        # Fetch a scalar that DEPENDS ON THE UPDATED PARAMS: XLA's while-loop
        # simplifier dead-code-eliminates carry elements that don't reach the
        # output, so fetching s.step alone would time an empty loop.
        leaf = jax.tree_util.tree_leaves(s.params)[0]
        return jnp.sum(jnp.abs(leaf.astype(jnp.float32)))

    def make_chain(k):
        return lambda: float(chain(state, batch, k))

    step_s = chained_seconds_per_call(make_chain, k_lo=K_LO, k_hi=K_HI,
                                      repeats=REPEATS)

    mem = device_memory_stats()
    peak_hbm_gib = mem.get("peak_bytes_in_use", 0) / 2**30

    kind = getattr(jax.devices()[0], "device_kind", "")
    # the one peak table (bf16; an unknown TPU raises, a CPU has no peak)
    peak = peak_flops_for(kind)
    achieved_tflops = flops_per_step / step_s / 1e12 if flops_per_step else 0.0
    mfu = achieved_tflops * 1e12 / peak if peak else None

    # Roofline probes measured IN THE SAME RUN: attained_* are what THIS
    # chip does on a plain matmul and a plain stream right now;
    # mfu_vs_attained is the program's efficiency against that.
    m = jnp.ones((4096, 4096), jnp.bfloat16)
    probe_mm = jax.jit(lambda x: jax.lax.fori_loop(
        0, 8, lambda i, a: (a + i * 1e-6) @ m, x))
    v = jnp.ones((40 * 2**20,), jnp.bfloat16)
    probe_ew = jax.jit(lambda x: jax.lax.fori_loop(
        0, 8, lambda i, a: a * 1.000001 + i * 1e-9, x))

    def t_of(fn, arg):
        float(jnp.sum(fn(arg).astype(jnp.float32)))  # compile + warm
        t0 = time.perf_counter()
        for _ in range(3):
            r = jnp.sum(fn(arg).astype(jnp.float32))
        float(r)
        return (time.perf_counter() - t0) / 3 / 8

    attained_tflops = 2 * 4096 ** 3 / t_of(probe_mm, m) / 1e12
    attained_gbps = 2 * v.nbytes / t_of(probe_ew, v) / 1e9
    mfu_attained = achieved_tflops / attained_tflops

    print(json.dumps(bench_record({
        "metric": "sceneflow_train_step_time",
        "value": round(step_s, 4),
        "unit": "s/step (batch 8, 320x720, 22 iters, bf16)",
        "vs_baseline": round((1.0 / step_s) / BASELINE_STEPS_PER_S, 3),
        "steps_per_s": round(1.0 / step_s, 3),
        "flops_per_step": flops_per_step,
        "achieved_tflops": round(achieved_tflops, 1),
        "mfu_vs_bf16_peak": round(mfu, 4) if mfu is not None else None,
        "attained_matmul_tflops": round(attained_tflops, 1),
        "attained_stream_gbps": round(attained_gbps, 1),
        "mfu_vs_attained": round(mfu_attained, 3),
        "device_kind": kind,
        "peak_hbm_gib": round(peak_hbm_gib, 2),
    })))


if __name__ == "__main__":
    main()
