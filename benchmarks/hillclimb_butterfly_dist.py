import os
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=512")

"""§Perf iteration D: distributed execution of one butterfly (monarch) FFN
layer — GSPMD partitioner vs explicit shard_map orchestration.

The paper's §IV insight restated one level up: generic block-oriented
machinery (here: the SPMD partitioner) mis-schedules butterfly structure;
explicit orchestration (tokens sharded, 30x-smaller factors replicated,
factor-grad psum only) recovers it.

    PYTHONPATH=src python -m benchmarks.hillclimb_butterfly_dist
"""

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core import api
from repro.launch import analysis
from repro.launch.mesh import make_production_mesh


def main():
    mesh = make_production_mesh()  # 16x16
    spec = api.LinearSpec(4096, 4096, "monarch")  # yi-6b-scale butterfly FFN
    pshape = jax.eval_shape(lambda: api.init_linear(jax.random.PRNGKey(0), spec))
    x = jax.ShapeDtypeStruct((16 * 4096, 4096), jnp.bfloat16)  # 65k tokens

    def fwd_loss(p, xl):
        y = api.apply_linear(p, spec, xl)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    grad_fn = jax.grad(fwd_loss, argnums=(0, 1))

    psh_rep = jax.tree.map(lambda s: NamedSharding(mesh, P()), pshape)
    psh_tp = {
        "r": NamedSharding(mesh, P(None, None, "model", None, "data")),
        "l": NamedSharding(mesh, P(None, None, "model", "data", None)),
    }
    xsh = NamedSharding(mesh, P(("data",)))

    rows = []
    for name, ps in (("partitioner-TP", psh_tp), ("partitioner-replicated", psh_rep)):
        co = (
            jax.jit(grad_fn, in_shardings=(ps, xsh), out_shardings=(ps, xsh))
            .lower(pshape, x)
            .compile()
        )
        rows.append((name, analysis.roofline(co, mesh.devices.size, 0.0)))

    shard_grad = jax.shard_map(
        grad_fn,
        mesh=mesh,
        in_specs=(P(), P(("data", "model"))),
        out_specs=(P(), P(("data", "model"))),
    )
    co = jax.jit(shard_grad).lower(pshape, x).compile()
    rows.append(("shard_map-replicated", analysis.roofline(co, mesh.devices.size, 0.0)))

    print("name,us_per_call,derived")
    base = rows[0][1]
    for name, rl in rows:
        print(
            f"hillclimbD/{name},{rl.t_step*1e6:.3f},"
            f"t_mem_ms={rl.t_memory*1e3:.3f} t_coll_ms={rl.t_collective*1e3:.3f} "
            f"speedup_vs_TP={base.t_step/rl.t_step:.1f}x"
        )


if __name__ == "__main__":
    main()
