"""Benchmark harness: one module per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run [figure ...]
"""
import sys

from benchmarks import (fig03_model, fig10_improvement, fig11_throughput,
                        fig12_latency, fig13_calvin, fig13_scalability,
                        fig14_overhead, fig15_replication, fig16_scalability,
                        roofline_report)
from benchmarks.common import emit
from repro import compile_cache

ALL = {
    "fig03": fig03_model, "fig10": fig10_improvement,
    "fig11": fig11_throughput, "fig12": fig12_latency,
    "fig13": fig13_calvin, "fig13_scal": fig13_scalability,
    "fig14": fig14_overhead, "fig15": fig15_replication,
    "fig16": fig16_scalability, "roofline": roofline_report,
}


def main() -> None:
    fig13_scalability.force_host_devices()
    compile_cache.enable()
    which = sys.argv[1:] or list(ALL)
    print("name,us_per_call,derived")
    for name in which:
        emit(ALL[name].run())


if __name__ == '__main__':
    main()
