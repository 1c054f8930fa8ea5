"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (us_per_call = wall time of the
benchmark body; derived = its headline metric) and writes full row
dumps under results/benchmarks/.
"""
from __future__ import annotations

import json
import os
import time

from benchmarks import (chaos_recovery, continuous_perf,
                        controller_dynamics, disagg_boundary,
                        fig3_throughput, fig4_tradeoff, fig5_landscape,
                        fleet_boundary, fleet_live, perf_variants,
                        roofline, rule_ablation, spec_decode,
                        table2_dual_path, table3_ablation)

OUT = os.environ.get("BENCH_OUT", "results/benchmarks")

_BENCHES = [
    ("table2_dual_path", table2_dual_path,
     lambda c: f"direct_speedup_x={c['speedup_distilbert']}"),
    ("table3_ablation", table3_ablation,
     lambda c: (f"time_saving={c['time_saving_pct']}%"
                f";admission={c['admission_rate']}"
                f";acc_drop={c['accuracy_drop_pp']}pp")),
    ("fig3_throughput", fig3_throughput,
     lambda c: f"batched_gain_x={c['batched_gain_x']}"),
    ("fig4_tradeoff", fig4_tradeoff,
     lambda c: f"joules_saving={c['avg_joules_saving_pct']}%"),
    ("fig5_landscape", fig5_landscape,
     lambda c: f"n_basins={c['n_basins']}"),
    ("controller_dynamics", controller_dynamics,
     lambda c: f"tau_monotone={c['tau_monotone_decreasing']}"),
    ("roofline", roofline,
     lambda c: (f"ok={c['n_ok']};fail={c['n_fail']};"
                f"bottlenecks={c['bottleneck_histogram']}")),
    ("perf_variants", perf_variants,
     lambda c: ";".join(f"{k}:{v['speedup_x']}x({v['best_variant']})"
                        for k, v in c.items())),
    ("rule_ablation", rule_ablation,
     lambda c: (f"le_saves={c['le_saves_energy']};"
                f"ge_saves={c['ge_saves_energy']};"
                f"ge_skips_easier={c['ge_skips_easier']}")),
    ("fleet_boundary", fleet_boundary,
     lambda c: (f"crossover_qps={c['crossover_qps']};"
                f"ea_vs_rr={c['energy_vs_rr_saving_pct']}%")),
    ("fleet_live", fleet_live,
     lambda c: (f"scenarios={len(c['scenarios_completed'])};"
                f"served_once={c['all_served_once']};"
                f"acc={c['mean_accuracy']}")),
    ("continuous_perf", continuous_perf,
     lambda c: (f"steps_gain_x={c['steps_per_s_gain_x']};"
                f"host_sync={c['host_sync_frac_fused']}"
                f"(was {c['host_sync_frac_legacy']});"
                f"paged_slots_x={c['paged_slots_gain_x']};"
                f"parity={c['greedy_tokens_identical']}")),
    ("disagg_boundary", disagg_boundary,
     lambda c: (f"parity={c['token_parity']};"
                f"wins_at={','.join(c['disagg_wins_at']) or 'none'}")),
    ("spec_decode", spec_decode,
     lambda c: (f"parity={c['token_parity_aligned']};"
                f"accept={c['best_spec_acceptance']};"
                f"j_saving={c['energy_saving_pct']}%;"
                f"cold_backoff={c['controller_backed_off_cold']}")),
    ("chaos_recovery", chaos_recovery,
     lambda c: (f"in_deadline={c['crash_and_flap_in_deadline_frac']};"
                f"once={c['all_served_once']};"
                f"retries={c['total_retries']}")),
]


def main() -> None:
    # cold-start hardening: a repeat of the full harness reads every
    # XLA compile from the persistent cache
    from repro.launch.compile_cache import enable_compilation_cache
    print(f"# compilation cache: {enable_compilation_cache()}")
    os.makedirs(OUT, exist_ok=True)
    print("name,us_per_call,derived")
    failures = 0
    for name, mod, derive in _BENCHES:
        t0 = time.perf_counter()
        try:
            rows = mod.run()
            chk = mod.check(rows)
            us = (time.perf_counter() - t0) * 1e6
            with open(os.path.join(OUT, f"{name}.json"), "w") as f:
                json.dump({"rows": rows, "check": chk}, f, indent=2,
                          default=str)
            print(f"{name},{us:.0f},{derive(chk)}")
        except Exception as e:  # pragma: no cover
            failures += 1
            us = (time.perf_counter() - t0) * 1e6
            print(f"{name},{us:.0f},ERROR:{type(e).__name__}:{e}")
    if failures:
        raise SystemExit(1)


if __name__ == "__main__":
    main()
