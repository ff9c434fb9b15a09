import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Cross-pod gradient-compression dry-run: int8 wire vs f32 all-reduce.

Lowers two versions of the cross-pod gradient mean on the multi-pod
(pod=2, data=16, model=16) mesh for a representative sharded gradient
bundle (64M params ~ one jamba layer-group shard):

* plain:      psum(grads) / 2 over "pod" (f32 ring all-reduce)
* compressed: repro.distributed.compression.compressed_psum (int8 gather
              + per-sender scales + error feedback)

and compares the per-device link bytes from the HLO.  Writes
artifacts/dryrun/grad_compression__multipod.json — referenced by
EXPERIMENTS §Perf (jamba O3).
"""

import json

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..distributed.compression import compressed_psum
from .hlo_cost import analyze_hlo
from .mesh import make_production_mesh

OUT = "artifacts/dryrun/grad_compression__multipod.json"


def main():
    mesh = make_production_mesh(multi_pod=True)
    # one gradient bundle: (8192, 8192) sharded (data, model) per pod
    g = jax.ShapeDtypeStruct((8192, 8192), jnp.float32)
    e = jax.ShapeDtypeStruct((8192, 8192), jnp.float32)
    spec = P("data", "model")
    sh = NamedSharding(mesh, spec)

    def plain(gg, ee):
        def body(x):
            return jax.lax.pmean(x, "pod")
        fn = jax.shard_map(body, mesh=mesh, in_specs=P("data", "model"),
                           out_specs=P("data", "model"), check_vma=False)
        return fn(gg), ee

    def compressed(gg, ee):
        def body(x, err):
            out, new_err = compressed_psum({"g": x}, {"g": err}, "pod")
            return out["g"], new_err["g"]
        fn = jax.shard_map(body, mesh=mesh,
                           in_specs=(P("data", "model"), P("data", "model")),
                           out_specs=(P("data", "model"), P("data", "model")),
                           check_vma=False)
        return fn(gg, ee)

    rec = {}
    for name, fn in (("plain_f32_allreduce", plain),
                     ("int8_gather_error_feedback", compressed)):
        with mesh:
            compiled = jax.jit(fn, in_shardings=(sh, sh)).lower(g, e).compile()
        cost = analyze_hlo(compiled.as_text())
        rec[name] = {
            "link_bytes_per_device": cost["total_link_bytes"],
            "by_kind": cost["collective_link_bytes"],
        }
        print(f"{name:30s} link bytes/device: "
              f"{cost['total_link_bytes']/1e6:9.2f} MB "
              f"{cost['collective_link_bytes']}")
    ratio = (rec["plain_f32_allreduce"]["link_bytes_per_device"]
             / max(rec["int8_gather_error_feedback"]
                   ["link_bytes_per_device"], 1))
    rec["wire_reduction_x"] = ratio
    print(f"cross-pod wire reduction: {ratio:.2f}x")
    os.makedirs(os.path.dirname(OUT), exist_ok=True)
    with open(OUT, "w") as f:
        json.dump(rec, f, indent=1)


if __name__ == "__main__":
    main()
