"""Benchmark harness — one module per paper table/figure and per
subsystem. Prints ``name,us_per_call,derived`` CSV rows
(paper-expected values embedded in the derived field) and writes each
module's results to ``BENCH_<module>.json`` at the repo root: the
``emit``-ed rows plus, when the module's ``run()`` returns a dict, that
machine-readable result record."""
from __future__ import annotations

import json
import pathlib
import sys
import traceback

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def _write_record(mod_name: str, result, rows: list[dict]) -> None:
    rec: dict = {"rows": rows}
    if isinstance(result, dict):
        rec["result"] = result
        # Surface the generating configuration (sizes, seeds) at the
        # top level so a record is reproducible without reading the
        # module source.
        if isinstance(result.get("config"), dict):
            rec["config"] = result["config"]
    path = REPO_ROOT / f"BENCH_{mod_name}.json"
    path.write_text(json.dumps(rec, indent=2) + "\n")


def main() -> None:
    from repro.runtime.compile_cache import setup_compile_cache

    setup_compile_cache()
    from . import (bulk_placement_bench, cms_case_study, common,
                   fig4_group_split, fig6_priority, fig7_8_queue_exec,
                   fig9_11_migration, hier_bench, migration_bench,
                   p2p_bench, scenarios_bench, serving_bench,
                   streaming_bench)

    print("name,us_per_call,derived")
    failures = 0
    for mod in (fig4_group_split, fig6_priority, fig7_8_queue_exec,
                fig9_11_migration, migration_bench, p2p_bench,
                streaming_bench, cms_case_study, bulk_placement_bench,
                hier_bench, scenarios_bench, serving_bench):
        short = mod.__name__.rsplit(".", 1)[-1]
        common.drain_records()
        try:
            result = mod.run()
        except Exception:  # noqa: BLE001 — report all benches
            failures += 1
            print(f"{mod.__name__},ERROR,", file=sys.stdout)
            traceback.print_exc()
            common.drain_records()
            continue
        _write_record(short, result, common.drain_records())
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
