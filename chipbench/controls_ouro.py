"""The controls of the looped configuration's reference check, each through
the harness's own comparison (``drivers/train.py:reference_check``, at the
configuration's own ``check``):

    python3 -m chipbench.controls_ouro --workload ouro-2.6b.train-s2048 \\
        --seeds 11,65537 --controls sound,bfloat16,three_passes

``sound`` is the program as the cell runs it, which has to pass.  Every
other control puts the plain reference itself where the program stands:
with one of ``reference_ouro.PLANTS`` planted in it (float32, exact
products: the distance a program with that fault would stand from the right
reference), or ``bfloat16``, the reference in bfloat16 throughout (values,
Adam's moments, products, attention; only the logits are taken to float32
for the loss, as the program takes them): the precision below the one the
configuration states.  Each of those has to come out refused.  One line of
JSON a seed and control, the comparison's own record in it.
"""
import argparse
import json
import os
import types

from chipbench import measure, reference, reference_ouro
from chipbench.catalog import ROOT, Catalog

CONTROLS = ("sound", "bfloat16") + reference_ouro.PLANTS


class ReferenceAsProgram:
    """Stands where ``train.Sessions`` stands in ``reference_check``: the
    "program" whose losses are compared is the reference's own Adam step
    (``reference.make_step``) under ``control``."""

    def __init__(self, kind, seed, control):
        self._kind, self._seed, self._control = kind, seed, control
        self.runner = self.state = None

    def initial_values(self, sizes):
        import jax
        init, _ = self._kind.program(sizes)
        return jax.block_until_ready(
            jax.jit(init)(jax.random.PRNGKey(self._seed)))

    def get(self, sizes):
        import jax
        import jax.numpy as jnp
        low = self._control == "bfloat16"
        opt, step = reference.make_step(
            self._kind.reference_loss(
                sizes, plant=None if low else self._control),
            sizes["deployment"]["optimizer"]["learning_rate"], chunk_rows=1)
        values = self.initial_values(sizes)
        if low:
            values = jax.tree_util.tree_map(
                lambda x: x.astype(jnp.bfloat16), values)
        self.state = (values, opt.init(values))

        def run(state, batch):
            with jax.default_matmul_precision(
                    "default" if low else "highest"):
                params, opt_state, loss = step(*state, batch)
            return (params, opt_state), {"loss": loss}
        self.runner = types.SimpleNamespace(step=run)
        return self


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
    sessions.runner = sessions.state = None
    return {"control": name, "seed": seed, "refused": not found["ok"],
            **found}


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
