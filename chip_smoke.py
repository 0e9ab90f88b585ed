"""Chip smoke test: the DIANA decision path and the served model on one TPU.

    python3 chip_smoke.py

Runs in one process on one chip, through the entry points a user calls:

  a. bulk   — ``DianaScheduler.place_batch`` at the paper's bulk size
              (10,000 jobs x 256 sites, seed 0) must give exactly the
              assignments and final site state of the sequential
              ``place`` loop.
  b. plane  — ``PlacementEngine.cost_matrix(backend="kernel")`` at
              10,000 x 256 and 10,000 x 10,000: the program holds the
              Pallas kernel, alive entries agree with the float64 NumPy
              plane, dead columns are +inf, and every job's kernel
              argmin costs (in float64) within float32 rounding of the
              true minimum.
  c. serve  — ``repro.launch.serve`` at the published full width of
              recurrentgemma-2b (bf16, random weights from seed 0):
              every request is served, and each request's first greedy
              token agrees with ``LM.forward`` at the last prompt
              position.

Each phase prints one JSON line with its checks; host wall times there
are informational. The last line is ``{"ok": true, "device": {...}}``
when every check passed. Without a TPU the script exits non-zero and
prints no result line.
"""
from __future__ import annotations

import copy
import json
import sys
import time
import traceback
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

BULK_JOBS, BULK_SITES = 10_000, 256
PLANE_JOBS, PLANE_SITES = 10_000, (256, 10_000)
SERVE_ARGV = ("--arch", "recurrentgemma-2b", "--no-reduced", "--requests", "8",
              "--slots", "4", "--prompt-len", "16", "--new-tokens", "16")
SEED = 0


def phase_bulk(jobs: int = BULK_JOBS, sites: int = BULK_SITES) -> dict:
    from benchmarks.bulk_placement_bench import _build
    from repro.core import DianaScheduler

    site_d, link_d, job_list = _build(jobs, sites, SEED)
    seq = DianaScheduler(copy.deepcopy(site_d), dict(link_d))
    t0 = time.perf_counter()
    seq_sites = [seq.place(j).site for j in copy.deepcopy(job_list)]
    seq_s = time.perf_counter() - t0
    bat = DianaScheduler(copy.deepcopy(site_d), dict(link_d))
    t0 = time.perf_counter()
    placement = bat.place_batch(copy.deepcopy(job_list))
    batch_s = time.perf_counter() - t0
    state = lambda d: [(s.queue_length, s.waiting_work) for s in d.sites.values()]
    return {
        "jobs": jobs, "sites": sites,
        "checks": {
            "identical_assignments": placement.sites == seq_sites,
            "identical_site_state": state(bat) == state(seq),
        },
        "host_wall_s_informational": {"sequential_place": seq_s,
                                      "place_batch": batch_s},
    }


def _plane(engine, jp, sp) -> dict:
    import jax
    import numpy as np

    from repro.kernels.cost_matrix.ops import cost_matrix_classed

    # the same call batched_cost_matrix(backend="kernel") makes
    args = (jp.bytes_, jp.work, jp.wcomp, jp.wdtc, sp.cap, sp.queue, sp.work,
            sp.load, sp.bw, sp.loss, sp.rtt, sp.alive, sp.mss)
    w = engine.weights
    kw = dict(w_queue=w.w_queue, w_work=w.w_work, w_load=w.w_load)
    has_kernel = "tpu_custom_call" in cost_matrix_classed.lower(*args, **kw).as_text()

    t0 = time.perf_counter()
    ck = engine.cost_matrix(jp, sp, backend="kernel")       # compiles
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    ck = engine.cost_matrix(jp, sp, backend="kernel")
    warm_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    jax.block_until_ready(cost_matrix_classed(*args, **kw))
    device_call_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    cn = engine.cost_matrix(jp, sp, backend="numpy")
    numpy_s = time.perf_counter() - t0

    alive = sp.alive
    J = ck.shape[0]
    picked = cn[np.arange(J), np.argmin(ck, axis=1)]
    best = cn.min(axis=1)
    err = np.abs(ck[:, alive] - cn[:, alive]) / np.abs(cn[:, alive])
    return {
        "jobs": J, "sites": ck.shape[1], "alive_sites": int(alive.sum()),
        "checks": {
            "lowered_has_tpu_custom_call": has_kernel,
            "alive_close_to_numpy": bool(np.allclose(
                ck[:, alive], cn[:, alive], rtol=2e-4, atol=1e-4)),
            "dead_columns_inf": bool(np.isposinf(ck[:, ~alive]).all()),
            "argmin_within_f32_rounding": bool(
                np.all(picked - best <= 1e-5 * np.abs(best))),
        },
        "max_rel_err": float(err.max()),
        "argmin_equal_to_numpy": int((picked == best).sum()),
        "host_wall_s_informational": {
            "kernel_first_call": first_s, "kernel_warm_call": warm_s,
            "kernel_jit_call_block_until_ready": device_call_s,
            "numpy_reference": numpy_s,
        },
    }


def phase_plane(jobs: int = PLANE_JOBS, site_counts=PLANE_SITES) -> dict:
    from benchmarks.bulk_placement_bench import _build
    from repro.core import PlacementEngine, SitePack

    engine = PlacementEngine()
    planes = []
    for sites in site_counts:
        site_d, link_d, job_list = _build(jobs, sites, SEED)
        planes.append(_plane(engine, engine.pack_jobs(job_list),
                             SitePack.from_scheduler(site_d, link_d)))
    checks = {f"{p['jobs']}x{p['sites']}:{k}": v
              for p in planes for k, v in p.pop("checks").items()}
    return {"checks": checks, "planes": planes}


def phase_serve(argv=SERVE_ARGV) -> dict:
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch import serve

    t0 = time.perf_counter()
    engine, reqs = serve.main(list(argv))
    serve_s = time.perf_counter() - t0
    lm, params, stats = engine.lm, engine.params, engine.stats
    cfg = lm.cfg

    prompts = jnp.asarray(np.stack([r.prompt for r in reqs]))
    logits, _ = jax.jit(lambda p, t: lm.forward(p, t, last_only=True))(params, prompts)
    logits = np.asarray(logits[:, 0], np.float32)
    first = np.asarray([r.generated[0] for r in reqs])
    top = logits.max(axis=1)
    got = logits[np.arange(len(reqs)), first]
    # random-init logits can tie closely: allow one bf16 ulp of the max
    ulp = np.exp2(np.floor(np.log2(np.abs(top))) - 7)
    dtypes = sorted({str(x.dtype) for x in jax.tree.leaves(params)})
    return {
        "arch": cfg.name, "layers": cfg.num_layers, "d_model": cfg.d_model,
        "vocab": cfg.vocab_size, "param_dtypes": dtypes,
        "params": int(sum(x.size for x in jax.tree.leaves(params))),
        "served": stats.served, "batches": stats.batches,
        "decode_steps": stats.decode_steps,
        "checks": {
            "served_all": stats.served == len(reqs),
            "not_truncated": not stats.truncated,
            "all_tokens_generated": all(
                len(r.generated) == r.max_new_tokens for r in reqs),
            "first_token_matches_forward": bool(np.all(got >= top - ulp)),
        },
        "first_token_is_forward_argmax": int((first == logits.argmax(axis=1)).sum()),
        "host_wall_s_informational": {"serve_main": serve_s},
    }


PHASES = (("a_bulk", phase_bulk), ("b_plane", phase_plane), ("c_serve", phase_serve))


def main() -> int:
    from repro.runtime.compile_cache import setup_compile_cache

    cache_dir = setup_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}", file=sys.stderr)
        return 1
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    print(json.dumps({"compile_cache": cache_dir, "device": device}), flush=True)
    all_passed = True
    for name, phase in PHASES:
        try:
            rec = phase()
            passed = all(rec["checks"].values())
        except Exception:  # noqa: BLE001 — report every phase, fail at the end
            traceback.print_exc()
            rec, passed = {"error": traceback.format_exc(limit=1)}, False
        stats = dev.memory_stats() or {}
        rec["device_peak_bytes_in_use"] = stats.get("peak_bytes_in_use")
        print(json.dumps({"phase": name, "passed": passed, **rec}), flush=True)
        all_passed &= passed
    if not all_passed:
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
