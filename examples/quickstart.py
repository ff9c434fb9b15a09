"""Quickstart — the e-GPU paper's workflow in five minutes, on one CPU.

1. configure an e-GPU (Table-II knobs),
2. run an OpenCL-style kernel through the Tiny-OpenCL (TinyCL) runtime,
3. read the paper-calibrated speed-up / energy report,
4. scale the SAME knob discipline up: one reduced LM arch, one train step.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCHS
from repro.core import (APU, EGPU_16T, EGPU_4T, Program, Stage,
                        characterize, egpu_active_power_mw)
from repro.data import DataConfig, SyntheticLMData
from repro.launch.compile_cache import enable_compile_cache
from repro.models import init_params, model_spec
from repro.optim import adamw_init, constant_schedule
from repro.train.step import TrainConfig, make_train_step


def main():
    enable_compile_cache()
    print("=" * 70)
    print("1) configure an e-GPU (paper Table II/III)")
    print("=" * 70)
    for cfg in (EGPU_4T, EGPU_16T):
        ch = characterize(cfg)
        print(f"  {cfg.name}: {cfg.compute_units} CUs x {cfg.threads_per_cu} "
              f"threads x {cfg.warps_per_cu} warps | "
              f"D$ {cfg.dcache_bytes//1024} "
              f"KiB/{cfg.dcache_banks} banks | {ch.total_area_mm2:.2f} mm2, "
              f"{egpu_active_power_mw(cfg):.1f} mW")

    print()
    print("=" * 70)
    print("2) offload a GeMM through TinyCL and compare against the host")
    print("=" * 70)
    rng = np.random.default_rng(0)
    a = jnp.asarray(rng.integers(-64, 64, (256, 256)), jnp.int32)   # int math:
    b = jnp.asarray(rng.integers(-64, 64, (256, 256)), jnp.int32)   # no FPU!
    apu = APU(EGPU_16T)
    # Tiny-OpenCL host API v2: build the program once, create kernel objects
    # from the registry (clCreateProgramWithBuiltInKernels / clCreateKernel)
    program = Program.build(EGPU_16T)
    print(f"  program kernels: {', '.join(program.kernel_names)}")
    stage = Stage(program.create_kernel("gemm"),
                  counts_params={"m": 256, "n": 256, "k": 256})
    # default NDRange = the paper's §VIII-B trick (work-items == hw threads,
    # each looping internally) — scheduling collapses to the constant ~25 us
    (out,), report = apu.offload([stage], (a, b))
    np.testing.assert_array_equal(out.data, np.asarray(a) @ np.asarray(b))
    st = report.stages[0]
    print(f"  C=A@B 256x256 int32 OK | modeled speed-up {st.speedup:.1f}x | "
          f"energy reduction {st.energy_reduction:.1f}x")
    print(f"  phases: sched {st.egpu.scheduling_fraction*100:.1f}% | "
          f"transfer {st.egpu.transfer_fraction*100:.1f}%")

    print()
    print("=" * 70)
    print("3) the same knob discipline at datacenter scale: one train step")
    print("=" * 70)

    cfg = ARCHS["qwen2.5-3b"].reduced()
    step = jax.jit(make_train_step(cfg, TrainConfig(remat="full"),
                                   constant_schedule(1e-3)))
    params = init_params(model_spec(cfg), jax.random.PRNGKey(0))
    state = {"params": params, "opt": adamw_init(params)}
    data = SyntheticLMData(DataConfig(4, 64, cfg.vocab), cfg)
    batch = {k: jnp.asarray(v) for k, v in data.batch_at(0).items()}
    state, metrics = step(state, batch)
    print(f"  {cfg.name}: loss {float(metrics['loss']):.3f}, "
          f"grad-norm {float(metrics['grad_norm']):.2f} — "
          "same remat/sharding "
          "knobs the 398B dry-run uses")
    print("\nquickstart OK")


if __name__ == "__main__":
    main()
