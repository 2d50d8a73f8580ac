"""The phases and blocks that the scopes of the ResNet cell's compiled step
name, at a test's size on four devices (``test_bench_scopes.py`` runs this
in a process of its own, which sees four virtual CPU devices)."""
import jax

from bench import harness
from bench import scopes as sc
from bench.tests import tiny


def main():
    assert len(jax.devices()) == 4, jax.devices()
    cell = tiny.cell("resnet50.b256.4chip", chips=4)
    plan = cell.program.plan(cell.sizes, cell.mix, harness.make_mesh(4))
    text = plan.fn.lower(*plan.abstract_args).compile().as_text()
    found = set().union(*map(sc.scope_names,
                             sc.scopes_from_hlo(text).values()))
    print("scopes:", " ".join(sorted(found)), flush=True)


if __name__ == "__main__":
    main()
