"""Tiny-OpenCL host API v2 (ISSUE 4): Program / KernelRegistry objects and
explicit buffer-transfer commands.

Pins the new contracts:

* every built-in kernel family builds through one registry on multiple
  ``EGPUConfig`` presets, numerically identical to a direct builder call,
  with ``(family, config, variant)`` memoization;
* clSetKernelArg-style ``arg_info`` / ``set_args`` / ``enqueue_kernel``;
* ``enqueue_write_buffer`` / ``read_buffer`` / ``copy_buffer`` return real
  transfer-only-costed events that compose with markers/barriers,
  ``wait_events`` and DAG capture (eager and graph modes), and the fused
  critical path overlaps transfer nodes with compute on independent
  branches;
* enforced ``Buffer`` flags, ``GraphBuffer`` flag inheritance, and the
  ``create_buffer`` copy/use_host_ptr fast paths.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (APU, EGPU_8T, EGPU_16T, Buffer, CommandQueue,
                        Context, Device, Kernel, NDRange, Program, Stage,
                        fuse_breakdowns, kernel_family, transfer_time)
from repro.core.program import BUILTIN_FAMILIES, KernelRegistry
from repro.kernels.gemm.ref import counts as gemm_counts
from repro.kernels.gemm.ref import gemm_ref

NDR = NDRange((8, 8), (4, 4))
CONFIGS = (EGPU_8T, EGPU_16T)


def _ctx(config=EGPU_16T):
    return Context(Device(config))


def _mm_kernel(name="mm"):
    return Kernel(name=name, executor=gemm_ref,
                  counts=lambda **kw: gemm_counts(m=8, n=8, k=8))


def _x(seed=0, shape=(8, 8)):
    return jnp.asarray(np.random.default_rng(seed).standard_normal(shape),
                       jnp.float32)


def _family_inputs(name):
    """Small sample invocation arrays per built-in family."""
    rng = np.random.default_rng(7)
    f32 = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)
    if name == "gemm":
        return (f32(16, 32), f32(32, 8))
    if name == "fir":
        return (f32(256), f32(16))
    if name == "delineate":
        return (f32(256),)
    if name == "stockham_fft":
        return (f32(128),)
    if name == "svm":
        return (f32(8, 12), f32(16, 12), f32(16), jnp.float32(0.1))
    if name == "mamba_scan":
        return (f32(1, 32, 4), jnp.abs(f32(1, 32, 4)) * 0.1,
                -jnp.abs(f32(4, 2)), f32(1, 32, 2), f32(1, 32, 2), f32(4))
    if name == "decode_attention":
        return (f32(1, 2, 8), f32(1, 2, 16, 8), f32(1, 2, 16, 8))
    raise AssertionError(f"no sample inputs for family {name!r}")


# ---------------------------------------------------------------------------
# Registry smoke: every family x >= 2 configs, legacy-identical, memoized
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("config", CONFIGS, ids=lambda c: c.name)
@pytest.mark.parametrize("family", sorted(BUILTIN_FAMILIES))
def test_registry_builds_every_family(family, config):
    program = Program.build(config)
    kern = program.create_kernel(family)
    assert kern.family == family and kern.config is config
    assert kern.counts is not None
    # memoized: a second program build hands out the SAME kernel object
    assert Program.build(config).create_kernel(family) is kern
    # numerically identical to a direct builder call (a fresh,
    # distinct kernel object that bypasses the registry memo)
    ops = importlib.import_module(BUILTIN_FAMILIES[family])
    legacy = ops.build_kernel(config)
    ins = _family_inputs(family)
    got, want = kern.executor(*ins), legacy.executor(*ins)
    got = got if isinstance(got, tuple) else (got,)
    want = want if isinstance(want, tuple) else (want,)
    for g, w in zip(got, want):
        assert np.array_equal(np.asarray(g), np.asarray(w))


def test_program_exposes_all_seven_builtin_families():
    program = Program.build(EGPU_16T)
    assert set(BUILTIN_FAMILIES) <= set(program.kernel_names)
    kernels = program.create_kernels()
    assert set(BUILTIN_FAMILIES) <= set(kernels)
    assert len(BUILTIN_FAMILIES) == 7


def test_variants_and_configs_are_distinct_memo_entries():
    p16, p8 = Program.build(EGPU_16T), Program.build(EGPU_8T)
    base = p16.create_kernel("gemm")
    assert base is p16.create_kernel("gemm", use_pallas=True)  # canonical
    assert base is not p16.create_kernel("gemm", use_pallas=False)
    assert base is not p8.create_kernel("gemm")
    with pytest.raises(KeyError):
        p16.create_kernel("no_such_family")


def test_private_registry_and_app_registration():
    reg = KernelRegistry()

    @kernel_family("app.scale", registry=reg)
    def build_scale(config, *, k=2.0):
        return Kernel("scale", executor=lambda x: x * k)

    prog = Program.build(EGPU_16T, registry=reg)
    assert prog.kernel_names == ("app.scale",)
    kern = prog.create_kernel("app.scale", k=3.0)
    np.testing.assert_allclose(np.asarray(kern.executor(jnp.ones(4))), 3.0)
    # double registration is loud (same name, different builder)
    with pytest.raises(ValueError):
        kernel_family("app.scale", registry=reg)(lambda config: None)


def test_tinybio_stage_kernels_are_stable_across_builds():
    from repro.apps.tinybio import tinybio_stages
    s1, _ = tinybio_stages(EGPU_16T)
    s2, _ = tinybio_stages(EGPU_16T)
    for a, b in zip(s1, s2):
        assert a.kernel is b.kernel, a.kernel.name


# ---------------------------------------------------------------------------
# clSetKernelArg-style introspection
# ---------------------------------------------------------------------------
def test_arg_info_classifies_buffers_and_params():
    kern = Program.build(EGPU_16T).create_kernel("svm")
    info = kern.arg_info
    assert [a.name for a in info if a.kind == "buffer"] == [
        "x", "sv", "alpha", "b"]
    assert [a.name for a in info if a.kind == "param"] == ["gamma"]
    # gamma is a defaulted positional: it may be fed as a buffer too
    assert kern.n_buffer_args == (4, 5)


def test_set_args_enqueue_kernel_matches_enqueue_nd_range():
    ctx = _ctx()
    q = CommandQueue(ctx)
    kern = _mm_kernel()
    a, b = _x(1), _x(2)
    kern.set_args(a, b)
    e1 = q.enqueue_kernel(kern, NDR)
    e2 = q.enqueue_nd_range(kern, NDR,
                            (ctx.create_buffer(a), ctx.create_buffer(b)))
    q.finish()
    assert np.array_equal(np.asarray(e1.outputs[0].data),
                          np.asarray(e2.outputs[0].data))
    assert e1.modeled is not None
    assert e1.modeled.total_cycles == e2.modeled.total_cycles


def test_set_arg_by_index_and_arity_errors():
    kern = Kernel("f", executor=lambda a, b, gamma=0.5: a * gamma)
    x = _x(3)
    kern.set_arg(0, x).set_arg(1, x).set_arg(2, 0.25)
    bufs, params = kern.staged_args()
    assert len(bufs) == 2 and params == {"gamma": 0.25}
    with pytest.raises(ValueError):
        kern.set_args(x)                     # too few buffers
    with pytest.raises(RuntimeError):
        Kernel("g", executor=lambda a, b: a).staged_args()


# ---------------------------------------------------------------------------
# Explicit transfer commands — eager mode
# ---------------------------------------------------------------------------
def test_write_read_copy_are_transfer_only_events():
    ctx = _ctx()
    q = CommandQueue(ctx)
    x = _x(4)
    dst = ctx.create_buffer(jnp.zeros_like(x))
    wev = q.enqueue_write_buffer(dst, x)
    expect = transfer_time(EGPU_16T, x.size * 4)
    assert wev.modeled.transfer == expect.transfer > 0
    assert wev.modeled.compute == wev.modeled.startup == 0.0
    assert wev.energy_j is not None and wev.energy_j > 0
    assert np.array_equal(np.asarray(dst.data), np.asarray(x))

    rev = q.enqueue_read_buffer(dst)
    assert rev.modeled.transfer == expect.transfer
    (out,) = rev.wait()
    assert np.array_equal(np.asarray(out.data), np.asarray(x))

    cpy = ctx.create_buffer(jnp.zeros_like(x))
    cev = q.enqueue_copy_buffer(dst, cpy)
    assert cev.modeled.transfer == expect.transfer
    q.finish()
    assert np.array_equal(np.asarray(cpy.data), np.asarray(x))
    # transfers are queue events: modeled totals include them
    assert q.total_modeled_s() >= 3 * expect.total_s


def test_transfers_chain_and_compose_with_markers_and_barriers():
    ctx = _ctx()
    q = CommandQueue(ctx, out_of_order=True)
    x = _x(5)
    buf = ctx.create_buffer(jnp.zeros_like(x))
    wev = q.enqueue_write_buffer(buf, x)
    # dataflow: a kernel consuming the written buffer depends on the write
    kev = q.enqueue_nd_range(_mm_kernel(), NDR, (buf, buf))
    assert wev in kev.deps
    # wait_events: a read ordered after the kernel via the explicit list
    rev = q.enqueue_read_buffer(kev.outputs[0], wait_events=[kev])
    assert kev in rev.deps
    m = q.enqueue_marker()               # aggregates everything so far
    assert set(m.deps) >= {wev, kev, rev}
    bar = q.enqueue_barrier()
    w2 = q.enqueue_write_buffer(ctx.create_buffer(jnp.zeros_like(x)), x)
    assert bar in w2.deps                # out-of-order: barrier edge only
    q.finish()
    assert all(e.done for e in (wev, kev, rev, w2))
    np.testing.assert_allclose(np.asarray(rev.outputs[0].data),
                               np.asarray(x) @ np.asarray(x), rtol=1e-5)


def test_in_order_queue_chains_transfers_implicitly():
    ctx = _ctx()
    q = CommandQueue(ctx)
    x = _x(6)
    b1 = ctx.create_buffer(jnp.zeros_like(x))
    e1 = q.enqueue_write_buffer(b1, x)
    e2 = q.enqueue_read_buffer(b1)
    assert e1 in e2.deps                 # implicit in-order edge
    e3 = q.enqueue_write_buffer(b1, x * 2, blocking=True)   # CL_TRUE
    assert e3.done
    np.testing.assert_allclose(np.asarray(b1.data), np.asarray(x) * 2)


def test_transfer_shape_dtype_validation():
    ctx = _ctx()
    q = CommandQueue(ctx)
    dst = ctx.create_buffer(jnp.zeros((8, 8), jnp.float32))
    with pytest.raises(ValueError, match="does not match"):
        q.enqueue_write_buffer(dst, jnp.zeros((4, 4), jnp.float32))
    with pytest.raises(ValueError, match="does not match"):
        q.enqueue_copy_buffer(dst, ctx.create_buffer(
            jnp.zeros((8, 8), jnp.int32)))


# ---------------------------------------------------------------------------
# Buffer flag enforcement
# ---------------------------------------------------------------------------
def test_flags_are_enforced():
    ctx = _ctx()
    q = CommandQueue(ctx)
    x = _x(7)
    ro = ctx.create_buffer(x, flags="r")
    wo = ctx.create_buffer(x, flags="w")
    rw = ctx.create_buffer(x)
    with pytest.raises(ValueError, match="read-only"):
        q.enqueue_write_buffer(ro, x)
    with pytest.raises(ValueError, match="read-only"):
        q.enqueue_copy_buffer(rw, ro)
    with pytest.raises(ValueError, match="write-only"):
        q.enqueue_read_buffer(wo)
    with pytest.raises(ValueError, match="write-only"):
        q.enqueue_nd_range(_mm_kernel(), NDR, (wo, rw))
    with pytest.raises(ValueError, match="write-only"):
        q.enqueue_copy_buffer(wo, rw)
    # the same contracts hold under capture
    with q.capture():
        with pytest.raises(ValueError, match="read-only"):
            q.enqueue_write_buffer(ro, x)
        with pytest.raises(ValueError, match="write-only"):
            q.enqueue_nd_range(_mm_kernel(), NDR, (wo, rw))
    with pytest.raises(ValueError):
        Buffer(x, flags="rx")


def test_graphbuffer_inherits_source_flags():
    ctx = _ctx()
    q = CommandQueue(ctx)
    x = _x(8)
    ro = ctx.create_buffer(x, flags="r")
    with q.capture() as g:
        rev = q.enqueue_read_buffer(ro)      # read from a read-only buffer
        kev = q.enqueue_nd_range(_mm_kernel(), NDR, (rev.outputs[0],
                                                     rev.outputs[0]))
    assert rev.outputs[0].flags == "r"       # inherited, not hardcoded "rw"
    assert kev.outputs[0].flags == "rw"      # kernel outputs stay fresh
    assert [n.kind for n in g.nodes] == ["read", "kernel"]


# ---------------------------------------------------------------------------
# Transfer commands under capture: graph nodes + critical-path overlap
# ---------------------------------------------------------------------------
def test_capture_records_transfer_nodes_and_matches_eager():
    ctx = _ctx()
    x = _x(9)
    q = CommandQueue(ctx)
    with q.capture() as g:
        buf = Buffer(jnp.zeros_like(x))
        q.enqueue_write_buffer(buf, x)
        kev = q.enqueue_nd_range(_mm_kernel(), NDR, (buf, buf),
                                 _resident=True)
        q.enqueue_read_buffer(kev.outputs[0])
    assert [n.kind for n in g.nodes] == ["write", "kernel", "read"]
    assert g.node_deps() == ((), (0,), (1,))
    assert g.nodes[0].nbytes == x.size * 4
    (out,) = g.launch()
    # the captured chain computes what the same kernel computes eagerly
    np.testing.assert_array_equal(np.asarray(out.data),
                                  np.asarray(gemm_ref(x, x)))
    # fused model prices the explicit traffic: write + read bytes over the
    # bus, with the kernel marked resident
    fused, _ = g.fused_modeled()
    assert fused.transfer == pytest.approx(
        2 * transfer_time(EGPU_16T, x.size * 4).transfer)


def test_capture_write_orders_after_readers_of_old_value():
    """Write-after-read: overwriting a buffer must depend on every captured
    node that consumed the OLD value, not just its producer — otherwise the
    critical path models the overwrite as concurrent with its readers."""
    ctx = _ctx()
    x = _x(17)
    q = CommandQueue(ctx, out_of_order=True)
    with q.capture() as g:
        buf = Buffer(jnp.zeros_like(x))
        q.enqueue_write_buffer(buf, x)               # 0: producer
        q.enqueue_read_buffer(buf)                   # 1: reader of old value
        q.enqueue_nd_range(_mm_kernel(), NDR, (buf, buf),
                           _resident=True)           # 2: reader of old value
        q.enqueue_write_buffer(buf, x * 2)           # 3: overwrite
    deps = g.node_deps()
    assert set(deps[3]) >= {1, 2}                    # WAR edges, not just {0}
    # flags still enforced on the write path's source buffer
    wo_src = ctx.create_buffer(x, flags="w")
    with pytest.raises(ValueError, match="write-only"):
        CommandQueue(ctx).enqueue_write_buffer(
            ctx.create_buffer(jnp.zeros_like(x)), wo_src)


def test_capture_copy_buffer_rebinds_destination():
    """A captured copy node: consumers of the destination observe the
    copied value, and the node models one bus transfer."""
    ctx = _ctx()
    x = _x(16)
    q = CommandQueue(ctx)
    with q.capture() as g:
        src = ctx.create_buffer(x)
        dst = Buffer(jnp.zeros_like(x))
        q.enqueue_copy_buffer(src, dst)
        kev = q.enqueue_nd_range(_mm_kernel(), NDR, (dst, dst),
                                 _resident=True)
        q.enqueue_read_buffer(kev.outputs[0])
    assert [n.kind for n in g.nodes] == ["copy", "kernel", "read"]
    assert g.nodes[0].nbytes == x.size * 4
    (out,) = g.launch()
    np.testing.assert_allclose(np.asarray(out.data),
                               np.asarray(x) @ np.asarray(x), rtol=1e-5)


def test_trailing_reads_define_graph_outputs():
    ctx = _ctx()
    x = _x(10)
    q = CommandQueue(ctx)
    with q.capture() as g:
        a = ctx.create_buffer(x)
        e1 = q.enqueue_nd_range(_mm_kernel("A"), NDR, (a, a))
        e2 = q.enqueue_nd_range(_mm_kernel("B"), NDR, (e1.outputs[0], a))
        q.enqueue_read_buffer(e1.outputs[0])
        q.enqueue_read_buffer(e2.outputs[0])
    outs = g.launch()
    assert len(outs) == 2                # one per trailing read, in order
    np.testing.assert_allclose(np.asarray(outs[0].data),
                               np.asarray(x) @ np.asarray(x), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(outs[1].data),
                               np.asarray(outs[0].data) @ np.asarray(x),
                               rtol=1e-4)


def test_critical_path_overlaps_branch_transfers_with_compute():
    """Acceptance: explicit transfer nodes on independent out-of-order
    branches overlap with compute in the fused critical path — the chain
    model (same nodes, serial) is strictly slower, and the critical path
    hides the smaller branch entirely."""
    ctx = _ctx()
    q = CommandQueue(ctx, out_of_order=True)
    xa, xb = _x(11), _x(12)
    with q.capture() as g:
        ba, bb = Buffer(jnp.zeros_like(xa)), Buffer(jnp.zeros_like(xb))
        q.enqueue_write_buffer(ba, xa)
        q.enqueue_write_buffer(bb, xb)
        ka = q.enqueue_nd_range(_mm_kernel("A"), NDR, (ba, ba),
                                _resident=True)
        kb = q.enqueue_nd_range(_mm_kernel("B"), NDR, (bb, bb),
                                _resident=True)
        q.enqueue_nd_range(_mm_kernel("combine"), NDR,
                           (ka.outputs[0], kb.outputs[0]),
                           wait_events=[ka, kb], _resident=True)
    kinds = [n.kind for n in g.nodes]
    assert kinds == ["write", "write", "kernel", "kernel", "kernel"]
    # two independent branches: write->kernel chains meeting at the combine
    assert g.node_deps() == ((), (), (0,), (1,), (2, 3))
    fused, _ = g.fused_modeled()
    chain = fuse_breakdowns(g.modeled_breakdowns())
    assert fused.total_s < chain.total_s
    # the critical path carries ONE branch (write + kernel) + combine; the
    # sibling branch's transfer happens during it
    per_write = g.nodes[0].modeled
    per_kernel = g.nodes[2].modeled
    assert fused.transfer == pytest.approx(per_write.transfer)
    assert fused.compute == pytest.approx(2 * per_kernel.compute)
    assert chain.transfer == pytest.approx(2 * per_write.transfer)
    # and the whole thing still computes the right numbers
    (out,) = g.launch()
    np.testing.assert_allclose(
        np.asarray(out.data),
        (np.asarray(xa) @ np.asarray(xa)) @ (np.asarray(xb) @ np.asarray(xb)),
        rtol=1e-4)


def test_apu_capture_pipeline_explicit_transfers():
    """The serving capture shape: write -> resident kernels -> read, with
    launch_prefix results bit-identical to the classic capture."""
    apu = APU(EGPU_16T)
    kern = apu.program.create_kernel("gemm")
    stages = [Stage(kern, counts_params={"m": 8, "n": 8, "k": 8}),
              Stage(kern, counts_params={"m": 8, "n": 8, "k": 8},
                    n_inputs=1, consts=(_x(14),))]
    x = _x(13)
    classic = apu.capture_pipeline(stages, (x, x))
    explicit = apu.capture_pipeline(stages, (x, x), explicit_transfers=True)
    assert [n.kind for n in explicit.nodes] == [
        "write", "write", "kernel", "kernel", "read"]
    # kernels are resident: no heuristic per-kernel transfer phase
    for node in explicit.nodes:
        if node.kind == "kernel":
            assert node.modeled.transfer == 0.0
    y = _x(15)
    got = explicit.launch_prefix([y, y])
    want = classic.launch_prefix([y, y])
    assert np.array_equal(np.asarray(got[0].data), np.asarray(want[0].data))
    # APU flag wires through offload and stays report-consistent
    apu2 = APU(EGPU_16T, explicit_transfers=True)
    outs, report = apu2.offload(stages, (x, x))
    assert np.array_equal(
        np.asarray(outs[0].data),
        np.asarray(apu.offload(stages, (x, x))[0][0].data))
    assert len(report.stages) == len(stages)
    assert report.egpu_fused is not None


# ---------------------------------------------------------------------------
# create_buffer fast paths (CL_MEM_USE_HOST_PTR)
# ---------------------------------------------------------------------------
def test_create_buffer_copy_and_use_host_ptr():
    ctx = _ctx()
    x = jnp.arange(16, dtype=jnp.float32)
    assert ctx.create_buffer(x).data is x            # jax.Array: adopted
    assert ctx.create_buffer(x, copy=False).data is x
    assert ctx.create_buffer(x, use_host_ptr=True).data is x
    assert ctx.create_buffer(x, copy=True).data is not x
    host = np.arange(4, dtype=np.float32)
    assert isinstance(ctx.create_buffer(host).data, jax.Array)
    with pytest.raises(TypeError):
        ctx.create_buffer(host, copy=False)          # cannot adopt numpy
    with pytest.raises(TypeError):
        ctx.create_buffer(host, use_host_ptr=True)
    with pytest.raises(ValueError):
        ctx.create_buffer(x, copy=True, use_host_ptr=True)
