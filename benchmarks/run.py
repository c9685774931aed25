"""Benchmark harness entry: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines.  ``--quick`` shrinks training
budgets (CI); default budgets reproduce the EXPERIMENTS.md numbers.
Benchmarks with machine-readable output (currently ``serve``) also write
``BENCH_<name>.json`` at the repo root via ``common.write_bench_json``.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: table1,fig6,fig7,transfer,roofline,"
                         "kernels,serve,spec,servek,servep,servec,servem,"
                         "serveg")
    ap.add_argument("--kernel", default="auto", choices=("auto", "interpret"),
                    help="Pallas mode of servek and the serveg kernel cell "
                         "('auto' compiles and needs a TPU)")
    args, _ = ap.parse_known_args()
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    only = set(args.only.split(",")) if args.only else None

    print("name,us_per_call,derived")

    def section(name):
        return only is None or name in only

    if section("table1"):
        from benchmarks.bench_table1_complexity import run as t1
        t1()
    if section("kernels"):
        from benchmarks.bench_kernels import run as bk
        bk()
    if section("roofline"):
        from benchmarks.bench_roofline import run as rf
        rf()
    if section("serve"):
        from benchmarks.bench_serve_engine import run as sv
        sv(quick=args.quick)
    if section("spec"):
        # speculative decode on the grown pair only (merges into the
        # serve JSON)
        from benchmarks.bench_serve_engine import run as sv_spec
        sv_spec(quick=args.quick, families=(), speculate=True)
    if section("servek"):
        # kernel-vs-jnp slot decode only (merges into the serve JSON)
        from benchmarks.bench_serve_engine import run as sv_kern
        sv_kern(quick=args.quick, families=(), kernel=args.kernel)
    if section("servep"):
        # dense-vs-paged slot pool pairs only (merges into the serve JSON)
        from benchmarks.bench_serve_engine import run as sv_pool
        sv_pool(quick=args.quick, families=(), pool=True)
    if section("servec"):
        # chaos/fault-tolerance sweep only (merges into the serve JSON)
        from benchmarks.bench_serve_engine import run as sv_chaos
        sv_chaos(quick=args.quick, families=(), chaos=True)
    if section("servem"):
        # sharded-vs-single-device mesh sweep only (needs 4 visible
        # devices; merges into the serve JSON)
        from benchmarks.bench_serve_engine import run as sv_mesh
        sv_mesh(quick=args.quick, families=(), mesh=True)
    if section("serveg"):
        # scenario sweep: families x pool x kernel x trace-shape matrix,
        # incl. mid-trace live-upgrade cells (merges into the serve JSON)
        from benchmarks.scenarios import run as sv_scen
        sv_scen(quick=args.quick, kernel=args.kernel)
    if section("fig6"):
        from benchmarks.bench_fig6_rank_ablation import run as f6
        f6(quick=args.quick)
    if section("fig7"):
        from benchmarks.bench_fig7_growth_curves import run as f7
        f7(quick=args.quick)
    if section("transfer"):
        from benchmarks.bench_transfer import run as tr
        tr(quick=args.quick)


if __name__ == "__main__":
    main()
