"""One cell, once: ``python3 -m chipbench.run --workload <cell> --seed <n>
--seconds <s> --trace <0|1>``.

Finds the chips, hands the cell to its traffic mix's driver, and prints the
result as one JSON object on the last line of standard output.  It sets no
platform and has no CPU mode: without a TPU, or with another number of
chips than the cell asks for, it says what JAX found and exits non-zero
before anything is compiled.
"""
import argparse
import json
import os
import sys
import time

from chipbench import measure
from chipbench.catalog import ROOT, Catalog


def result_line(catalog, cell, run, device, trace):
    """The contract's last line from a driver's record: the cell's
    end-to-end metrics untraced, its per-layer metrics traced, and last
    what decided ``correct``: each check by name, with the numbers it
    compared and their limits."""
    if trace:
        wanted = {m["name"] for m in
                  catalog.metric_specs("per_layer", cell["name"])}
        metrics = {}
        for reader in catalog.layer_metrics():
            value = reader.read(run) if reader.NAME in wanted else None
            if value is not None:
                metrics[reader.NAME] = {"value": value, "unit": reader.UNIT}
    else:
        metrics = {m["name"]: {"value": run["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in catalog.metric_specs("end_to_end", cell["name"])}
    checks = {name: f"{'ok' if ok else 'FAILED'}: {detail}"
              for name, (ok, detail) in run["checks"].items()}
    for name, said in checks.items():
        print(f"chipbench: check {name}: {said}", flush=True)
    device = dict(device, memory_peak_bytes=run["memory_peak_bytes"])
    line = {"correct": all(ok for ok, _ in run["checks"].values()),
            "attempted": run["attempted"], "failed": run["failed"],
            "metrics": metrics, "device": device}
    reduced = run["trace"]
    if trace and reduced is not None:
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
        line["breakdown"] = {
            "device_ops": [list(x) for x in reduced["op_seconds"]],
            "idle_gaps": [list(x) for x in reduced["longest_gaps"]]}
    line["checks"] = checks
    return line


def run_cell(catalog, cell, *, seed, seconds, trace, clock0):
    """The cell's record from its driver, and the result line."""
    import jax
    devices = jax.devices()
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices)}
    driver = catalog.module("drivers", cell["mix"]["driver"])
    run = driver.run(cell, seed=seed, seconds=seconds, trace=trace,
                     catalog=catalog, clock0=clock0)
    return result_line(catalog, cell, run, device, trace)


def main(argv=None):
    clock0 = (time.perf_counter(), measure.process_age_s())
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    catalog = Catalog()
    cell = catalog.cell(args.workload)
    # What the program writes besides the compile cache (strategies, logs,
    # its transform report) stays inside the checkout; read at its import.
    os.environ.setdefault("AUTODIST_WORKING_DIR",
                          os.path.join(ROOT, ".chipbench_work"))
    import jax
    devices = jax.devices()
    print(f"chipbench: JAX found {len(devices)} x "
          f"{devices[0].device_kind!r} ({devices[0].platform})", flush=True)
    if devices[0].platform != "tpu":
        sys.exit(f"chipbench: no TPU was found; cell {cell['name']!r} is "
                 f"measured on {cell['chips']} chip(s) and nowhere else")
    if len(devices) != cell["chips"]:
        sys.exit(f"chipbench: cell {cell['name']!r} asks for "
                 f"{cell['chips']} chip(s) and this machine holds "
                 f"{len(devices)}")
    catalog.peak(devices[0].device_kind)
    from autodist_tpu.utils import compile_cache
    print(f"chipbench: compile cache at {compile_cache.enable()}", flush=True)

    line = run_cell(catalog, cell, seed=args.seed, seconds=args.seconds,
                    trace=bool(args.trace), clock0=clock0)
    print(json.dumps(line), flush=True)
    # The same again where a record of a run that is not correct keeps it.
    for name, said in line["checks"].items():
        print(f"chipbench: check {name}: {said}", file=sys.stderr, flush=True)


if __name__ == "__main__":
    main()
