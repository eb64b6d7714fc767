"""Benchmark driver: one section per paper table/figure + the beyond-paper
feature benches.  Emits ``name,value,derived`` CSV rows."""

import time


def main() -> None:
    from . import (fig3_kernel_ratio, fig4_transfer_ratio, fig5_ma_task,
                   fig6_mm_task, pipeline_partition_bench, placement_bench,
                   serve_sched_bench)
    print("name,value,derived")
    for mod in (fig3_kernel_ratio, fig4_transfer_ratio, fig5_ma_task,
                fig6_mm_task, pipeline_partition_bench, placement_bench,
                serve_sched_bench):
        t0 = time.time()
        mod.main()
        print(f"bench.{mod.__name__.split('.')[-1]}.wall_s,"
              f"{time.time()-t0:.1f},", flush=True)


if __name__ == "__main__":
    main()
