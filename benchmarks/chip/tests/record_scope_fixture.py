#!/usr/bin/env python3
"""Records ``fixtures/scopes_v5e.json`` on one TPU chip: two traced steps
of a tiny robust step with the Pallas kernels, each after a separately
jitted batch program whose instruction names collide with the step's.

  python3 benchmarks/chip/tests/record_scope_fixture.py [OUT]

The fixture holds the reduced trace (``trace_reduce.Trace``), the
device's "XLA Modules" line (which program ran when: the answer the
reduction must find without it), for each program loaded the
``op_name`` of every instruction label the trace shows, and the bytes
of each Pallas call (``trace_reduce.custom_call_bytes``).
"""
from __future__ import annotations

import glob
import json
import re
import shutil
import sys
import tempfile
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parent))
sys.path.insert(0, str(HERE.parents[2] / "src"))


def main(out: str) -> int:
    import jax
    from jax.profiler import ProfileData

    import scope_reduce as S
    import trace_reduce as T
    from repro import models as MD
    from repro.configs.base import ArchConfig, RobustConfig
    from repro.dist import init_train_state, jit_train_step, make_train_step
    from repro.optim import constant, sgd

    if jax.devices()[0].platform != "tpu":
        print("record_scope_fixture: no TPU", file=sys.stderr)
        return 2
    arch = ArchConfig(name="scope-tiny", family="dense", n_layers=2,
                      d_model=128, n_heads=2, n_kv_heads=2, d_ff=256,
                      vocab_size=512)
    n, f, b, s = 7, 1, 2, 64
    opt = sgd(momentum=0.9)
    step = jit_train_step(make_train_step(
        arch, RobustConfig(n_workers=n, f=f, gar="multi_bulyan",
                           use_pallas=True),
        opt, constant(0.05), chunk_q=s, attack="sign_flip"))

    @jax.jit
    def batch(key, i):
        k = jax.random.fold_in(key, i)
        tok = jax.random.randint(k, (n, b, s + 1), 0, arch.vocab_size)
        return {"tokens": tok[..., :-1], "labels": tok[..., 1:]}

    key = jax.random.key(0)
    params = MD.init_model(key, arch)
    state = init_train_state(opt, params, n_workers=n)
    params, state, _ = step(params, state, batch(key, 0), key)
    jax.block_until_ready(params)
    tmp = tempfile.mkdtemp()
    span = jax.profiler.TraceAnnotation
    jax.profiler.start_trace(tmp)
    try:
        with span("bench:window"):
            for i in range(1, 3):
                with span("bench:step"):
                    params, state, _ = step(params, state, batch(key, i),
                                            jax.random.fold_in(key, i))
                    jax.block_until_ready(params)
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)
    trace = T.load_xplane(path)
    modules = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name == "/device:TPU:0":
            modules = [[re.sub(r"\(\d+\)$", "", e.name), float(e.start_ns),
                        float(e.duration_ns)]
                       for line in plane.lines if line.name == "XLA Modules"
                       for e in line.events]
    shutil.rmtree(tmp)
    seen = {e.name for e in trace.devices[0]}
    programs = []
    for name, ops in S.live_programs(jax):
        ops = {k: v for k, v in ops.items() if k in seen}
        if ops:
            programs.append([name, ops])
    kernel_bytes = {}
    for exe in jax.devices()[0].client.live_executables():
        for m in exe.hlo_modules():
            kernel_bytes.update(T.custom_call_bytes(m.to_string()))
    with open(out, "w") as fh:
        json.dump({"trace": trace.to_json(), "modules": modules,
                   "programs": programs, "kernel_bytes": kernel_bytes}, fh)
    print(f"record_scope_fixture: {len(trace.devices[0])} events, "
          f"{len(modules)} module runs, programs "
          f"{[(p[0], len(p[1])) for p in programs]} -> {out}")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else str(HERE / "fixtures" / "scopes_v5e.json")))
