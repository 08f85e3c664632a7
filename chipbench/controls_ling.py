"""The controls of the Ling configuration's reference check, each through
the harness's own comparison (``drivers/train.py:reference_check``, at the
configuration's own ``check``), as ``controls_ouro.py`` runs the looped
configuration's:

    python3 -m chipbench.controls_ling --workload \\
        ling-3.0-flash-vl.train-s2048 --seeds 11,65537 \\
        --controls sound,bfloat16,scalar_decay

``sound`` is the program as the cell runs it, which has to pass.  Every
other control puts the plain reference itself where the program stands:
with one of ``reference_kda_mla_moe.PLANTS`` planted in it (float32, exact
products: the distance a program with that fault would stand from the right
reference), or ``bfloat16``, the reference in bfloat16 throughout (values,
Adam's moments, one-pass products; ``controls_ouro.ReferenceAsProgram``): the
precision below the one the configuration states.  Each of those has to come out refused.  One line of
JSON a seed and control, the comparison's own record in it.
"""
import argparse
import json
import os

from chipbench import measure, reference_kda_mla_moe
from chipbench.catalog import ROOT, Catalog
from chipbench.controls_ouro import ReferenceAsProgram

CONTROLS = ("sound", "bfloat16") + reference_kda_mla_moe.PLANTS
PARTS = ("xent", "moe.held_output_rms", "attn.output_std", "kda.output_std",
         "kda.gate_min", "kda.state_absmax", "moe.groups_reached",
         "moe.held_assignments")


def control(catalog, cell, seed, name, steps=None):
    """``reference_check``'s record of ``cell`` on ``seed`` with control
    ``name`` in the program's place (``steps`` for the configuration's
    own number of them, where given)."""
    import jax
    import numpy as np
    train = catalog.module("drivers", cell["mix"]["driver"])
    sizes, mix = cell["sizes"], cell["mix"]
    if steps:
        sizes = {**sizes, "check": {**sizes["check"], "steps": steps}}
    kind = catalog.module("kinds", sizes["kind"])
    rows = mix["rows_per_chip"] * len(jax.devices())
    spans = measure.Spans()
    if name == "sound":
        example = kind.host_batch(sizes, mix, rows,
                                  np.random.RandomState([seed, 1]))
        sessions = train.Sessions(kind, example, seed, spans)
    else:
        sessions = ReferenceAsProgram(kind, seed, name)
    found = train.reference_check(kind, sizes, mix, rows, seed, sessions,
                                  spans)
    # What the program's last step read of the number's parts.
    parts = {k: float(v) for k, v in (
        getattr(sessions.runner, "last_aux", None) or {}).items()
        if k in PARTS}
    sessions.runner = sessions.state = None
    return {"control": name, "seed": seed, "refused": not found["ok"],
            **found, **({"parts": parts} if parts else {})}


def main(argv=None):
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", required=True,
                        type=lambda text: [int(s) for s in text.split(",")])
    parser.add_argument("--controls", default=",".join(CONTROLS),
                        type=lambda text: text.split(","))
    parser.add_argument("--steps", type=int, default=None)
    args = parser.parse_args(argv)
    catalog = Catalog()
    os.environ.setdefault("AUTODIST_WORKING_DIR",
                          os.path.join(ROOT, ".chipbench_work"))
    from autodist_tpu.utils import compile_cache
    compile_cache.enable()
    cell = catalog.cell(args.workload)
    for seed in args.seeds:
        for name in args.controls:
            print("chipbench: control " + json.dumps(
                control(catalog, cell, seed, name, args.steps)), flush=True)


if __name__ == "__main__":
    main()
