"""TinyBio — the paper's Fig-4 application, end to end.

Runs the 4-stage biosignal pipeline (FIR band-pass → peak/trough
delineation → Stockham-FFT spectral features → SVM cognitive-workload
decision) on every e-GPU configuration, printing the per-stage speed-up /
energy table the paper reports, plus the functional outputs.

Run:  PYTHONPATH=src python examples/tinybio_pipeline.py
"""

import numpy as np

from repro.apps.tinybio import TINYBIO_WORKLOAD, run_tinybio
from repro.core import EGPU_4T, EGPU_8T, EGPU_16T
from repro.launch.compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    print(f"workload: {TINYBIO_WORKLOAD}")
    print()
    header = f"{'config':10s} {'stage':15s} {'speed-up':>9s} {'energy x':>9s}"
    for cfg in (EGPU_4T, EGPU_8T, EGPU_16T):
        decisions, report = run_tinybio(cfg)
        print(header)
        for st in report.stages:
            print(f"{cfg.name:10s} {st.name:15s} {st.speedup:8.2f}x "
                  f"{st.energy_reduction:8.2f}x")
        print(f"{cfg.name:10s} {'WHOLE APP':15s} "
              f"{report.overall_speedup:8.2f}x "
              f"{report.overall_energy_reduction:8.2f}x")
        pos = int((np.asarray(decisions) > 0).sum())
        print(f"  -> {pos}/{decisions.shape[0]} windows classified "
              f"high-workload\n")

    print("paper Fig 4: fir 3.6-15.1x | delineation 3.1-13.1x | fft 3.3-14.0x "
          "| app 3.4-14.3x | energy 1.7-3.1x")


if __name__ == "__main__":
    main()
