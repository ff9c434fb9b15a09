"""compressed_psum under shard_map: correctness on a real (1-device) mesh
and int8-wire verification on the lowered multipod HLO."""

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.distributed.compression import compressed_psum
from repro.launch.mesh import make_host_mesh


def test_compressed_psum_single_participant_exact():
    """N=1: the mean equals the dequantized local grad (within 1 LSB)."""
    mesh = make_host_mesh()
    g = {"w": jnp.asarray(np.random.default_rng(0).standard_normal((8, 128)),
                          jnp.float32)}
    e = {"w": jnp.zeros((8, 128), jnp.float32)}

    def body(gg, ee):
        return compressed_psum(gg, ee, "data")

    fn = jax.shard_map(body, mesh=mesh,
                       in_specs=(P(), P()), out_specs=(P(), P()),
                       check_vma=False)
    out, err = fn(g, e)
    np.testing.assert_allclose(np.asarray(out["w"]) + np.asarray(err["w"]),
                               np.asarray(g["w"]), rtol=1e-5, atol=1e-5)


def test_compressed_wire_is_int8_in_jaxpr():
    """The gathered collective payload is int8, not f32 (a 1-device mesh
    elides the gather in HLO, so inspect the jaxpr)."""
    mesh = make_host_mesh()
    g = jnp.zeros((1024,), jnp.float32)
    e = jnp.zeros((1024,), jnp.float32)

    def body(gg, ee):
        return compressed_psum({"w": gg}, {"w": ee}, "data")

    fn = jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                       out_specs=(P(), P()), check_vma=False)
    jaxpr = str(jax.make_jaxpr(fn)(g, e))
    assert "all_gather" in jaxpr
    # the big gathered operand is int8; only the (1,)-scale gathers are f32
    import re
    ops = re.findall(r"(\w+)\[[^\]]*1024[^\]]*\] = all_gather", jaxpr)
    assert ops and all(o == "i8" for o in ops), jaxpr[:800]
