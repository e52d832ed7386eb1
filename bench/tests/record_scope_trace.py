#!/usr/bin/env python3
"""Record the small TPU trace that ``test_bench_scopes.py`` reads.

    PYTHONPATH=src python bench/tests/record_scope_trace.py [out_dir]

A jitted training step whose forward runs under two of the program's named
scopes (``attn`` and ``loss``; the gradient's ops carry the ``jvp(...)`` and
``transpose(jvp(...))`` forms), called three times, each inside a
``repro.obs`` span ``bench.call`` with the tracer enabled, so each call is
also a profiler annotation.  Around the calls, a ``bench.window``
annotation with a ``perf_counter_ns`` reading taken as it opens, as the
benchmark's runner takes it.  Writes ``scopes.xplane.pb`` and
``scopes.json`` (the ring's spans in ns from that reading) to ``out_dir``,
by default ``bench/tests/data``.  Exits 2 off a TPU.
"""

import glob
import json
import os
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp

from repro import obs


def loss_fn(w, x):
    with jax.named_scope("attn"):
        h = jnp.tanh(x @ w)
    with jax.named_scope("loss"):
        return jnp.mean(h * h)


@jax.jit
def scoped_step(w, x):
    loss, g = jax.value_and_grad(loss_fn)(w, x)
    return w - 0.1 * g, loss


def main(out_dir: str) -> int:
    if jax.devices()[0].platform != "tpu":
        print(f"no TPU: {jax.devices()[0].platform}", file=sys.stderr)
        return 2
    w = jnp.ones((512, 512), jnp.float32) * 0.01
    x = jnp.ones((256, 512), jnp.float32)
    jax.block_until_ready(scoped_step(w, x))          # compile outside
    tmp = tempfile.mkdtemp()
    tr = obs.enable()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    window = jax.profiler.TraceAnnotation("bench.window")
    origin_ns = time.perf_counter_ns()
    window.__enter__()
    for _ in range(3):
        with obs.span("bench.call"):
            w, loss = scoped_step(w, x)
            jax.block_until_ready(loss)
        time.sleep(0.005)
    window.__exit__(None, None, None)
    jax.profiler.stop_trace()
    obs.disable()
    spans = [[tr._epoch_ns + e.ts_us * 1e3 - origin_ns,
              tr._epoch_ns + (e.ts_us + e.dur_us) * 1e3 - origin_ns, e.name]
             for e in tr.events()]
    os.makedirs(out_dir, exist_ok=True)
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, os.path.join(out_dir, "scopes.xplane.pb"))
    with open(os.path.join(out_dir, "scopes.json"), "w") as f:
        json.dump({"device_kind": jax.devices()[0].device_kind,
                   "spans": spans}, f, indent=1)
    shutil.rmtree(tmp, ignore_errors=True)
    print(os.path.getsize(os.path.join(out_dir, "scopes.xplane.pb")),
          "bytes;", len(spans), "spans")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1] if len(sys.argv) > 1
                  else os.path.join(os.path.dirname(__file__), "data")))
