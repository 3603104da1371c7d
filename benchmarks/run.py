# One function per paper table/figure. Prints ``figure,metric,policy,value``
# CSV rows; roofline terms are derived from the dry-run artifacts when
# present (run ``python -m repro.launch.dryrun --all`` first for those).
# Exits non-zero when any figure raised.
from __future__ import annotations

import os
import sys
import time
import traceback


def main() -> int:
    from benchmarks import figures
    from benchmarks.common import emit
    from repro.common.cache import enable_compilation_cache

    enable_compilation_cache()   # repeat runs skip the XLA cold compiles
    t00 = time.time()
    failed = []
    print("figure,metric,policy,value")
    for fn in (figures.fig3_incast,
               figures.fig4_single_switch_collectives,
               figures.fig5_7_clos_queues,
               figures.fig8_completion,
               figures.fig9_pfc_counts,
               figures.fig10_dlrm_e2e,
               figures.fig11_static_window,
               figures.fig12_fabric_sweep,
               figures.fig13_fault_regimes):
        t0 = time.time()
        try:
            emit(fn())
        except Exception:
            print(f"{fn.__name__},ERROR,-,1")
            traceback.print_exc()
            failed.append(fn.__name__)
        emit([(fn.__name__, "wall_s", "-", round(time.time() - t0, 1))])

    # model roofline (reads dry-run artifacts if present)
    from benchmarks import roofline
    if os.path.isdir("experiments/dryrun") and os.listdir("experiments/dryrun"):
        print("--- roofline (from dry-run artifacts) ---")
        roofline.main()
    else:
        print("roofline,SKIPPED (run: python -m repro.launch.dryrun --all)")
    emit([("all", "total_wall_s", "-", round(time.time() - t00, 1))])
    if failed:
        print(f"figures failed: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
