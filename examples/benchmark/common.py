"""Shared benchmark driver (parity: /root/reference/examples/benchmark/).

Every benchmark: build a zoo model, pick a strategy by name, train with
synthetic data through the full pipeline, report steady-state throughput.
"""
import argparse
import time

import jax
import numpy as np
import optax

from autodist_tpu import AutoDist
from autodist_tpu.data import DevicePrefetcher
from autodist_tpu.utils import compile_cache
from autodist_tpu.strategy import (AllReduce, PS, PSLoadBalancing, Parallax,
                                   PartitionedAR, PartitionedPS,
                                   RandomAxisPartitionAR, UnevenPartitionedPS,
                                   ModelParallel)

STRATEGIES = {
    "PS": PS,
    "PSLoadBalancing": PSLoadBalancing,
    "PartitionedPS": PartitionedPS,
    "UnevenPartitionedPS": UnevenPartitionedPS,
    "AllReduce": AllReduce,
    "PartitionedAR": PartitionedAR,
    "RandomAxisPartitionAR": RandomAxisPartitionAR,
    "Parallax": Parallax,
    "ModelParallel": ModelParallel,
}


def parse_args(default_strategy="AllReduce", default_batch=64,
               transformer=False):
    """``transformer=True`` (the lm1b/bert drivers) adds the attention
    knobs; other models would parse-but-ignore them, silently wasting
    devices (--seq_parallel carves a mesh axis ResNet never uses)."""
    p = argparse.ArgumentParser()
    p.add_argument("--strategy", default=default_strategy,
                   choices=sorted(STRATEGIES))
    p.add_argument("--batch_size", type=int, default=default_batch)
    p.add_argument("--steps", type=int, default=50)
    p.add_argument("--warmup", type=int, default=5)
    p.add_argument("--optimizer", default="adam")
    p.add_argument("--lr", type=float, default=1e-3)
    p.add_argument("--resource_spec", default=None)
    p.add_argument("--precision", default=None, choices=["bf16"],
                   help="bf16 = mixed precision (bf16 compute, f32 master)")
    if transformer:
        p.add_argument("--attn", default="auto", choices=["auto", "dense"],
                       help="'auto' = the model's resolution (strategy "
                            "ring/ulysses, else fused Pallas flash on "
                            "TPU); 'dense' forces the O(s^2) reference "
                            "attention — the comparison baseline whose "
                            "VJP hits the HBM wall near seq 16k")
        p.add_argument("--seq_parallel", type=int, default=0,
                       help="carve a ring-attention 'seq' mesh axis of "
                            "this size (sequence parallelism for long "
                            "context); composes with --strategy as the "
                            "base")
    p.add_argument("--trace_dir", default=None,
                   help="jax.profiler trace output dir")
    args = p.parse_args()
    if (getattr(args, "seq_parallel", 0)
            and getattr(args, "attn", "auto") != "auto"):
        p.error("--seq_parallel wires ring attention through the parallel "
                "context; combine it with --attn auto")
    return args


def attn_fn_from_args(args):
    """The model-level attention hook implied by ``--attn`` (None = the
    model's own resolution, which already picks strategy ring/ulysses or
    the fused flash kernels).  'dense' returns the masked reference —
    explicit hooks receive the model's boolean mask, which the flash
    wrapper would refuse, so dense is the only meaningful override
    here."""
    if getattr(args, "attn", "auto") == "dense":
        from autodist_tpu.models import layers as L
        return L.dot_product_attention
    return None


def make_optimizer(args):
    return {"adam": optax.adam, "sgd": optax.sgd,
            "adamw": optax.adamw}[args.optimizer](args.lr)


def run_benchmark(name, args, params, loss_fn, batch_iter, example_batch):
    compile_cache.enable()
    builder = STRATEGIES[args.strategy]()
    if getattr(args, "seq_parallel", 0):
        from autodist_tpu.strategy import SequenceParallel
        builder = SequenceParallel(attn="ring",
                                   seq_axis=args.seq_parallel, base=builder)
    ad = AutoDist(resource_spec_file=args.resource_spec,
                  strategy_builder=builder)
    item = ad.capture(loss_fn, params, make_optimizer(args),
                      example_batch=example_batch,
                      precision=getattr(args, "precision", None))
    runner = ad.create_distributed_session(item)
    state = runner.create_state()

    feed = DevicePrefetcher(batch_iter, runner.remapper, depth=2)
    for _ in range(args.warmup):
        state, metrics = runner.step(state, next(feed), shard_inputs=False)
    jax.block_until_ready(metrics["loss"])

    if args.trace_dir:
        jax.profiler.start_trace(args.trace_dir)
    t0 = time.perf_counter()
    for _ in range(args.steps):
        state, metrics = runner.step(state, next(feed), shard_inputs=False)
    jax.block_until_ready(metrics["loss"])
    dt = time.perf_counter() - t0
    if args.trace_dir:
        jax.profiler.stop_trace()

    ips = args.batch_size * args.steps / dt
    dev = jax.devices()[0]
    print(f"{name} on {len(jax.devices())} x {dev.device_kind} "
          f"({dev.platform}) strategy={args.strategy} "
          f"batch={args.batch_size} "
          f"steps={args.steps}: {ips:.1f} samples/sec "
          f"({dt / args.steps * 1e3:.1f} ms/step, "
          f"loss={float(jax.device_get(metrics['loss'])):.4f})")
    return ips


def forever(make_batch):
    while True:
        yield make_batch()
