"""fsync(level) synchronization domains — the paper's §3.2 programmability.

    PYTHONPATH=src python examples/sync_domains.py

Demonstrates, on an 8-device host mesh, what the paper's Figure 2 shows in
hardware: disjoint subtrees of the synchronization tree operating as
independent BSP groups.

  * fsync(level) tokens: level ℓ returns 2^ℓ (the domain size);
  * two level-2 domains all-reduce gradients INDEPENDENTLY (different
    domain means ⇒ different results per domain);
  * escalating to the root level merges them into one global BSP group.
"""

import os

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax                     # noqa: E402
import jax.numpy as jnp        # noqa: E402
import numpy as np             # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.launch.mesh import make_mesh     # noqa: E402
from repro.core import collectives as C      # noqa: E402
from repro.core.barrier import SyncDomainMesh  # noqa: E402
from repro.core.tree import FractalTree      # noqa: E402


def main():
    mesh = make_mesh((2, 4), ("pod", "data"))
    sdm = SyncDomainMesh(mesh, ("pod", "data"))
    tree = sdm.tree
    print(f"mesh {dict(mesh.shape)} → {tree.num_levels}-level sync tree")
    for lvl in range(tree.num_levels + 1):
        print(f"  level {lvl}: domains of {tree.domain_size(lvl)} = "
              f"{[d for d in tree.domains(lvl)][:4]}"
              f"{' …' if len(tree.domains(lvl)) > 4 else ''}")

    # per-device gradient stand-ins: device i holds value i
    x = jnp.arange(8.0).reshape(8, 1)
    spec = P(("pod", "data"))

    def run(level):
        def f(v):
            tok = sdm.fsync(level)                      # barrier
            # all-reduce scoped to the fsync domain: recursive doubling over
            # the first `level` levels of the tree (root level = global)
            axes = ("pod", "data")
            red = v
            for b in range(level):
                perm = [(i, i ^ (1 << b)) for i in range(8)]
                red = red + jax.lax.ppermute(red, axes, perm)
            return red + 0 * tok
        return jax.jit(jax.shard_map(
            f, mesh=mesh, in_specs=spec, out_specs=spec, check_vma=False,
            axis_names=frozenset(("pod", "data"))))(x)

    for level in (1, 2, 3):
        out = np.asarray(run(level)).ravel()
        print(f"fsync(level={level}) domain-scoped sums per device: "
              f"{out.tolist()}")

    print("\nlevel 2: two independent domains (sums 0+1+2+3 and 4+5+6+7);")
    print("level 3: one global BSP group (sum 28 everywhere).")


if __name__ == "__main__":
    main()
