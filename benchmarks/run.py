"""Benchmark harness: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--only table2,fig4]

Prints ``name,us_per_call,derived`` CSV rows.
"""

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table2,table3,fig4,kernels,engine,"
                         "serve,persist,cluster,roofline")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = (args.only.split(",") if args.only
             else ["fig4", "kernels", "engine", "serve", "persist",
                   "cluster", "table2", "table3", "roofline"])
    from . import (cluster_bench, engine_bench, fig4, kernels_bench,
                   persist_bench, roofline_table, serve_bench, table2,
                   table3)
    mods = {"table2": table2, "table3": table3, "fig4": fig4,
            "kernels": kernels_bench, "engine": engine_bench,
            "serve": serve_bench, "persist": persist_bench,
            "cluster": cluster_bench, "roofline": roofline_table}
    print("name,us_per_call,derived")
    for n in names:
        mods[n].main()


if __name__ == '__main__':
    main()
