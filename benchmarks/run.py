# One function per paper table/figure. Prints ``name,us_per_call,derived`` CSV.
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

from benchmarks import figures as F


def main() -> None:
    suites = [
        F.fig3a_gemm_ipc,
        F.fig10_conv_ipc,
        F.fig11_pool_ipc,
        F.fig12_ratio_sweep,
        F.fig13_e2e_ipc,
        F.fig14_mem_accesses,
        F.fig15_latency,
        F.table2_engine_bandwidth,
        F.kernel_bench,
        F.step_bench,
        F.sealed_step_bench,
    ]
    if os.environ.get("RUN_SECURITY", "quick") != "skip":
        suites.append(lambda: F.security_fig8_fig9(
            quick=os.environ.get("RUN_SECURITY", "quick") == "quick"))
    print("name,us_per_call,derived")
    for suite in suites:
        for name, us, derived in suite():
            print(f"{name},{us},{derived}")


if __name__ == "__main__":
    main()
