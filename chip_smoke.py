"""Chip smoke: the lm1b train path and the flash-attention kernels on a TPU.

One process drives every local chip.  It trains the dense ``lm1b``
configuration at full width through the calls a user makes (``AutoDist`` ->
``capture`` -> ``create_distributed_session`` -> ``create_state`` ->
``DevicePrefetcher`` -> ``runner.step``), then compiles the Pallas kernels
on their own and compares them with the dense reference.  Every check that
fails ends the run with a non-zero exit code and a message naming the
check; nothing here catches an error to carry on.  It sets no platform:
without a TPU it stops before anything is compiled.

    python chip_smoke.py          # on the chip; on a CPU it exits non-zero

The last line of standard output is ``{"ok": true, "device": {...}}``; the
full report goes to ``chiprun_out/chip_smoke.json``.  The timings here are
facts of a bring-up (did it compile, is the clock believable), not
performance results.
"""
import importlib.metadata
import itertools
import json
import math
import os
import re
import statistics
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import optax

from autodist_tpu import AutoDist
from autodist_tpu.data import DevicePrefetcher
from autodist_tpu.models import lm
from autodist_tpu.observability import goodput
from autodist_tpu.ops.flash_attention import (_dense_reference,
                                              _flash_attention_packed,
                                              flash_attention)
from autodist_tpu.report import collective_summary
from autodist_tpu.strategy import PartitionedPS
from autodist_tpu.utils import compile_cache

SEED = 0
OUT_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "chiprun_out")
# (batch, heads, seq, head width): the lm1b attention shape, the long
# sequence at head width 128 that the next configurations need, and short
# rows, where a program takes several (batch, head) rows (16 of these 96):
# the grouped Mosaic lowering, which the CPU tests only interpret.
KERNEL_SHAPES = ((2, 16, 512, 64), (1, 16, 4096, 128), (8, 12, 128, 64))
# The same (batch, heads, seq, head_dim) read as the projections write them,
# (batch, seq, heads x head_dim), two heads a 128-lane block: one batch row a
# program, and four sharing one.
PACKED_KERNEL_SHAPES = ((2, 16, 1024, 64), (8, 12, 128, 64))
# bf16 outputs against an f32 reference of the same bf16 inputs: one bf16
# rounding is 2^-8 relative, sums over keys add a little.
KERNEL_ATOL = KERNEL_RTOL = 2e-2
KERNEL_NAMES = ("flash_fwd", "flash_bwd_dq", "flash_bwd_dkv")


def check(name, ok, detail):
    """Print a passed check; end the run, naming the check, on a failed one."""
    if not ok:
        sys.exit(f"chip_smoke: check '{name}' FAILED: {detail}")
    print(f"chip_smoke: ok {name}: {detail}", flush=True)


def device_report():
    """What JAX found, and the versions that found it."""
    devices = jax.devices()
    versions = {"jax": jax.__version__,
                "jaxlib": importlib.metadata.version("jaxlib")}
    try:
        versions["libtpu"] = importlib.metadata.version("libtpu")
    except importlib.metadata.PackageNotFoundError:
        versions["libtpu"] = "not installed"
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices), "versions": versions}


def kernel_calls(hlo_text):
    """``[(kernel name, [operand shapes])]`` for each Mosaic custom call in
    a compiled executable's text.  The name is the ``name=`` the kernel was
    built with (``ops/flash_attention.py``), read from the scope before
    ``/pallas_call`` whether bare (``attn/flash_fwd``) or wrapped by a
    transform (``transpose(jvp(flash_bwd_dq))``)."""
    calls = []
    for line in hlo_text.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in line:
            continue
        name = re.search(r'op_name="[^"]*?(\w+)\)*/pallas_call"', line)
        operands = line.split("operand_layout_constraints={", 1)
        shapes = []
        if len(operands) == 2:
            shapes = [tuple(int(d) for d in dims.split(",") if d)
                      for dims in re.findall(r"\w+\[([\d,]*)\]\{",
                                             operands[1].split("}}", 1)[0])]
        calls.append((name.group(1) if name else "", shapes))
    return calls


def train_phase(cfg, batch_size, seq, steps, learning_rate=1e-4):
    """Train ``cfg`` for one warm-up and ``steps`` timed steps on one
    repeated batch over every local device; returns the report."""
    devices = jax.devices()
    n = len(devices)
    ad = AutoDist(strategy_builder=PartitionedPS())
    params = lm.init(jax.random.PRNGKey(SEED), cfg)
    n_params = sum(int(np.prod(p.shape))
                   for p in jax.tree_util.tree_leaves(params))
    batch = lm.synthetic_batch(cfg, batch_size, seq, seed=SEED)
    item = ad.capture(lm.make_loss_fn(cfg), params,
                      optax.adam(learning_rate), example_batch=batch)
    # lm.init left the values on the first device; the program has them
    # now, and this reference would keep a second copy of the model there.
    del params
    runner = ad.create_distributed_session(item)
    state = runner.create_state()
    feed = DevicePrefetcher(itertools.repeat(batch), runner.remapper,
                            depth=2, pull_in_background=False)

    t0 = time.perf_counter()
    state, metrics = runner.step(state, next(feed), shard_inputs=False)
    losses = [float(jax.block_until_ready(metrics["loss"]))]
    compile_s = time.perf_counter() - t0

    step_ms = []
    for _ in range(steps):
        device_batch = next(feed)
        t0 = time.perf_counter()
        state, metrics = runner.step(state, device_batch,
                                     shard_inputs=False)
        jax.block_until_ready(metrics["loss"])
        step_ms.append((time.perf_counter() - t0) * 1e3)
        losses.append(float(metrics["loss"]))

    t0 = time.perf_counter()
    hlo = runner._aot_executable(device_batch).as_text()
    aot_s = time.perf_counter() - t0
    calls = kernel_calls(hlo)

    leaves = [x for x in jax.tree_util.tree_leaves(state)
              if isinstance(x, jax.Array)]
    opt_leaves = jax.tree_util.tree_leaves(state.opt_state)
    memory = [d.memory_stats() for d in devices]
    flops_per_step = 6.0 * n_params * batch_size * seq
    ms = statistics.median(step_ms)
    return {
        "devices": n,
        "mesh": dict(runner.program.mesh.shape),
        "lowering_path": ("explicit" if runner.program.use_explicit_path
                          else "gspmd"),
        "n_params": n_params,
        "global_batch": batch_size, "seq": seq, "heads": cfg.num_heads,
        "compile_and_first_step_s": round(compile_s, 2),
        "aot_text_s": round(aot_s, 2),
        "step_ms": [round(x, 2) for x in step_ms],
        "step_ms_median": round(ms, 2),
        "model_flops_per_step": flops_per_step,
        "achieved_flops_per_s": flops_per_step / (ms / 1e3),
        "losses": [round(x, 4) for x in losses],
        "state_leaves": len(leaves),
        "state_leaves_spanning_all_devices": sum(
            len(x.sharding.device_set) == n for x in leaves),
        "opt_state_bytes": sum(x.nbytes for x in opt_leaves),
        "opt_state_bytes_on_first_device": sum(
            x.addressable_shards[0].data.nbytes for x in opt_leaves),
        "bytes_in_use": [m and m["bytes_in_use"] for m in memory],
        "peak_bytes_in_use": [m and m["peak_bytes_in_use"] for m in memory],
        "kernel_calls": {name: sum(c[0] == name for c in calls)
                         for name in sorted({c[0] for c in calls})},
        # operand 0 is the scalar-prefetch offsets, operand 1 is q
        "kernel_q_shapes": sorted({c[1][1] for c in calls}),
        "collectives": collective_summary(
            hlo, ops=("reduce-scatter", "all-gather", "all-reduce"),
            keep_zeros=True),
    }


def check_train(report, vocab, peak_flops_per_device):
    """Every claim the train phase has to support, on the chip."""
    n = report["devices"]
    losses = report["losses"]
    check("loss-finite", all(math.isfinite(x) for x in losses),
          f"losses {losses}")
    check("loss-start", abs(losses[0] - math.log(vocab)) < 0.5,
          f"first loss {losses[0]} against ln({vocab}) = "
          f"{math.log(vocab):.2f}")
    check("loss-falls", losses[-1] < losses[0],
          f"last {losses[-1]} below first {losses[0]}")

    check("kernels-compiled",
          all(report["kernel_calls"].get(k) for k in KERNEL_NAMES),
          f"Mosaic custom calls in the step: {report['kernel_calls']}")
    # A device's share of the batch: (rows, seq, heads x d) in the kernels'
    # packed layout, (rows x heads, seq, d) in the split one.
    rows = report["global_batch"] // n
    q_rows = sorted({shape[0] for shape in report["kernel_q_shapes"]})
    check("kernel-operands-per-device",
          q_rows in ([rows], [rows * report["heads"]]),
          f"q operands {report['kernel_q_shapes']}; global batch "
          f"{report['global_batch']} / {n} devices = {rows} rows of "
          f"{report['heads']} heads")

    peak = peak_flops_per_device * n
    floor_ms = report["model_flops_per_step"] / peak * 1e3
    check("clock", report["achieved_flops_per_s"] <= peak,
          f"{report['step_ms_median']} ms a step is "
          f"{report['achieved_flops_per_s'] / 1e12:.1f} TFLOP/s of "
          f"{peak / 1e12:.0f} peak on {n} device(s) (no step can take "
          f"under {floor_ms:.1f} ms)")

    in_use = report["bytes_in_use"]
    check("memory-reported", all(in_use),
          f"bytes_in_use per device {in_use}, peak "
          f"{report['peak_bytes_in_use']}")
    if n == 1:
        return
    check("lowering-path", report["lowering_path"] == "explicit",
          f"{report['lowering_path']} on mesh {report['mesh']}")
    check("state-spans-devices",
          report["state_leaves_spanning_all_devices"]
          == report["state_leaves"],
          f"{report['state_leaves_spanning_all_devices']} of "
          f"{report['state_leaves']} state leaves on all {n} devices")
    share = (report["opt_state_bytes_on_first_device"]
             / report["opt_state_bytes"])
    check("opt-state-sharded", share <= 1.05 / n,
          f"one device holds {share:.3f} of the optimizer state "
          f"(1/{n} = {1 / n:.3f})")
    check("memory-balanced", max(in_use) <= 2 * min(in_use),
          f"bytes_in_use max/min = {max(in_use) / min(in_use):.2f}")
    coll = report["collectives"]
    check("collectives", coll["reduce-scatter"] and coll["all-gather"],
          f"{coll}")


def kernel_phase(shape, interpret, packed=False):
    """``flash_attention`` (causal, bf16, default blocks) against the dense
    reference at one shape: forward and ``jax.grad``.  With ``packed`` the
    kernels read the operands as (batch, seq, heads, head_dim), the layout
    ``models.layers.mha`` hands the flash hook.  Returns the largest error
    of each output and the Mosaic custom calls in the two executables."""
    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(SEED), 4)
    q, k, v = (jax.random.normal(key, shape, jnp.bfloat16)
               for key in (kq, kk, kv))
    cot = jax.random.normal(kc, shape, jnp.float32)

    def swap(x):
        return x.transpose(0, 2, 1, 3)

    def flash(q, k, v):
        if not packed:
            return flash_attention(q, k, v, True, interpret=interpret)
        return swap(_flash_attention_packed(
            swap(q), swap(k), swap(v), True, min(512, shape[2]),
            min(1024, shape[2]), interpret))

    def dense(q, k, v):
        return _dense_reference(q, k, v, True)

    def grads_of(fn):
        return jax.grad(
            lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * cot),
            argnums=(0, 1, 2))

    fwd = jax.jit(flash).lower(q, k, v).compile()
    bwd = jax.jit(grads_of(flash)).lower(q, k, v).compile()
    got = (fwd(q, k, v),) + tuple(bwd(q, k, v))
    # The reference sees the same bf16 values, held in f32, with exact f32
    # matmuls: what is left is the kernel's own error.
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    with jax.default_matmul_precision("highest"):
        want = (jax.jit(dense)(*f32),) + tuple(jax.jit(grads_of(dense))(*f32))
    errors, within = {}, {}
    for name, a, b in zip(("o", "dq", "dk", "dv"), got, want):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        errors[name] = float(np.max(np.abs(a - b)))
        within[name] = bool(np.all(
            np.abs(a - b) <= KERNEL_ATOL + KERNEL_RTOL * np.abs(b)))
    return {"shape": shape, "layout": "packed" if packed else "split",
            "max_abs_error": errors, "within": within,
            "finite": bool(all(np.isfinite(np.asarray(a, np.float32)).all()
                               for a in got)),
            "kernel_calls": [name for name, _ in
                             kernel_calls(fwd.as_text())
                             + kernel_calls(bwd.as_text())]}


def check_kernel(report):
    tag = "x".join(str(d) for d in report["shape"])
    if report["layout"] == "packed":
        tag += "-packed"
    check(f"kernel-compiled-{tag}",
          sorted(report["kernel_calls"]) == sorted(
              ("flash_fwd",) + KERNEL_NAMES),
          f"Mosaic custom calls {report['kernel_calls']}")
    check(f"kernel-parity-{tag}",
          report["finite"] and all(report["within"].values()),
          f"max abs error against the dense reference "
          f"{report['max_abs_error']} (atol {KERNEL_ATOL}, rtol "
          f"{KERNEL_RTOL})")


def main():
    device = device_report()
    print(f"chip_smoke: {json.dumps(device)}", flush=True)
    if device["platform"] != "tpu":
        sys.exit(f"chip_smoke: check 'platform' FAILED: no TPU was found "
                 f"(JAX reports {device['count']} x {device['kind']!r})")
    tflops = next((tf for needle, tf in goodput.PEAK_TFLOPS_TABLE
                   if needle in device["kind"].lower()), None)
    check("peak-table", tflops is not None,
          f"device kind {device['kind']!r} in goodput.PEAK_TFLOPS_TABLE: "
          f"{tflops} TFLOP/s a device")
    peak = tflops * 1e12
    print(f"chip_smoke: compile cache at {compile_cache.enable()}",
          flush=True)

    cfg = lm.lm1b()
    train = train_phase(cfg, batch_size=16, seq=512, steps=8)
    print(f"chip_smoke: train {json.dumps(train)}", flush=True)
    check_train(train, cfg.vocab, peak)

    kernels = []
    for shape, packed in ([(shape, False) for shape in KERNEL_SHAPES]
                          + [(shape, True) for shape in PACKED_KERNEL_SHAPES]):
        kernels.append(kernel_phase(shape, interpret=False, packed=packed))
        print(f"chip_smoke: kernel {json.dumps(kernels[-1])}", flush=True)
        check_kernel(kernels[-1])

    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump({"device": device, "train": train, "kernels": kernels}, f,
                  indent=1)
    print(json.dumps({"ok": True, "device": {
        "platform": device["platform"], "kind": device["kind"],
        "count": device["count"]}}))


if __name__ == "__main__":
    main()
