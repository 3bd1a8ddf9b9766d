"""Fig 2 reproduction: GAR aggregation time as a function of (n, d).

Paper protocol (§V-A): n gradients ~ U(0,1)^d; 7 timed runs per (n, d);
drop the 2 farthest from the median; report mean±std of the remaining 5.
Hardware differs (the paper uses a GTX 1080 Ti; this container is CPU-only)
so absolute times differ — the claims under test are the SHAPES:

* O(d) scaling: aggregation time linear in d for every rule (Thm 2(ii));
* O(n²) scaling in the number of workers for (MULTI-)KRUM/BULYAN;
* MEDIAN's advantage shrinks as d grows (the paper's crossover argument).

On top of the paper's grid this times the apply substrates for
multi_bulyan — ``[xla]`` (unfused tensordots + coordinate phase),
``[pallas]`` (materialised einsums + coord_select kernel), ``[fused]``
(single fused_select kernel, no (θ, d) HBM intermediates) and ``[sharded]``
(the whole stats→plan→apply pipeline mesh-native through shard_map over
the host mesh — DESIGN.md §10) — and persists everything to
``BENCH_agg_time.json`` so later PRs have a perf trajectory to diff
against (schema: rule -> "n=<n>,d=<d>" -> us_per_call).  On CPU the
Pallas rows run in interpret mode and the sharded row usually sees a 1×1
host mesh: those absolute numbers measure schedule + partitioning
overhead, not the hardware — the TPU claims are the HBM-traffic count and
the n/W row-block split of the distance phase.

CSV: name,us_per_call,derived
"""
from __future__ import annotations

import functools
import json
import time
from typing import Dict, List, Tuple

import numpy as np
import jax
import jax.numpy as jnp

from repro.core import api, gar

# CPU-sized version of the paper's grid (paper: n up to 39, d up to 1e7)
NS = (7, 11, 15, 19, 23)
DS = (100_000, 1_000_000)
RULES = ("median", "multi_krum", "multi_bulyan")
# apply-substrate comparison rows (the fused-path trajectory).  Timed on
# a reduced (n, d) product — n ∈ {11, 15} × d ∈ {4096, 1e5, 1e6}:
# interpret-mode Pallas costs hundreds of ms per call at d=1e6, so the
# full Fig-2 grid would dwarf the rule rows.  The d=4096 cell anchors the
# small-d end; the deep cells are the monotonicity evidence (us_per_call/d
# non-increasing — validate_bench gates on it).
PATHS = (
    ("multi_bulyan[xla]", dict(use_pallas=False, fused=False)),
    ("multi_bulyan[pallas]", dict(use_pallas=True, fused=False)),
    ("multi_bulyan[fused]", dict(use_pallas=True, fused="force")),
    ("multi_bulyan[sharded]", dict(sharded=True)),
)
PATH_NS = (11, 15)
PATH_DS = (4096,) + DS
BENCH_JSON = "BENCH_agg_time.json"

SMOKE_NS = (11,)
SMOKE_DS = (4096,)


def _f_for(n: int) -> int:
    return max(1, (n - 3) // 4)  # the paper's f = floor((n-3)/4)


def _timed(fn, *args, reps: int = 7, drop: int = 2) -> Tuple[float, float]:
    out = fn(*args)
    jax.block_until_ready(out)  # compile + warm
    times = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append(time.perf_counter() - t0)
    times = np.asarray(times)
    med = np.median(times)
    keep = times[np.argsort(np.abs(times - med))][: reps - drop]
    return float(keep.mean()), float(keep.std())


def _path_fn(f: int, sharded: bool = False, **kw):
    if sharded:
        from repro.launch.mesh import make_host_mesh
        kw["mesh_ctx"] = api.MeshContext.for_mesh(make_host_mesh())
    return jax.jit(functools.partial(
        api.aggregate_tree, f=f, name="multi_bulyan", **kw))


def write_json(results: Dict[str, Dict[Tuple[int, int], float]],
               path: str = BENCH_JSON) -> None:
    payload = {
        "schema": "rule -> 'n=<n>,d=<d>' -> us_per_call",
        "results": {
            rule: {f"n={n},d={d}": us * 1e6 for (n, d), us in grid.items()}
            for rule, grid in results.items()
        },
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)


def run(csv_rows: List[str], *, smoke: bool = False,
        json_path: str = BENCH_JSON) -> Dict[str, Dict[Tuple[int, int], float]]:
    rng = np.random.default_rng(0)
    ns, ds = (SMOKE_NS, SMOKE_DS) if smoke else (NS, DS)
    path_ns = ns if smoke else PATH_NS
    reps, drop = (3, 1) if smoke else (7, 2)
    path_reps, path_drop = (3, 1) if smoke else (5, 1)
    rows = list(RULES) + [name for name, _ in PATHS]
    results: Dict[str, Dict[Tuple[int, int], float]] = {r: {} for r in rows}
    jitted = {name: jax.jit(gar.get_gar(name), static_argnames=("f",))
              for name in RULES}
    for d in ds:
        for n in ns:
            G = jnp.asarray(rng.uniform(size=(n, d)).astype(np.float32))
            f = _f_for(n)
            for name in RULES:
                mean, std = _timed(lambda g: jitted[name](g, f=f), G,
                                   reps=reps, drop=drop)
                results[name][(n, d)] = mean
                csv_rows.append(
                    f"agg_time/{name}/n={n}/d={d},{mean*1e6:.1f},"
                    f"std_us={std*1e6:.1f}")
    path_ds = ds if smoke else PATH_DS
    for d in path_ds:
        for n in path_ns:
            G = jnp.asarray(rng.uniform(size=(n, d)).astype(np.float32))
            f = _f_for(n)
            for name, kw in PATHS:
                mean, std = _timed(_path_fn(f, **kw), G,
                                   reps=path_reps, drop=path_drop)
                results[name][(n, d)] = mean
                csv_rows.append(
                    f"agg_time/{name}/n={n}/d={d},{mean*1e6:.1f},"
                    f"std_us={std*1e6:.1f}")
    # derived claims (full grid only — the smoke grid has a single point)
    if not smoke:
        for name in RULES:
            r = results[name]
            # O(d): time(d=1e6)/time(d=1e5) ≈ 10 for linear scaling (n = 15)
            ratio_d = r[(15, ds[1])] / max(r[(15, ds[0])], 1e-9)
            csv_rows.append(f"agg_time/{name}/d_scaling_ratio,{ratio_d:.2f},"
                            f"linear_target=10.0")
        # crossover: median vs multi_bulyan advantage shrinking with d
        for d in ds:
            adv = results["median"][(15, d)] / results["multi_bulyan"][(15, d)]
            csv_rows.append(
                f"agg_time/median_over_multibulyan/d={d},{adv:.3f},"
                "higher_means_mb_faster")
        # fusion win: fused vs two-step pallas apply at the largest point
        big = (max(path_ns), max(path_ds))
        speedup = (results["multi_bulyan[pallas]"][big]
                   / max(results["multi_bulyan[fused]"][big], 1e-9))
        csv_rows.append(f"agg_time/fused_over_pallas_speedup,{speedup:.2f},"
                        "interpret_mode_schedule_only")
    write_json(results, json_path)
    return results


if __name__ == "__main__":
    rows: List[str] = []
    run(rows)
    print("\n".join(rows))
