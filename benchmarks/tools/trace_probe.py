#!/usr/bin/env python3
"""Run one small jitted program under the profiler on whatever device
JAX has, list the capture's planes and lines, reduce it with
``readers/xplane.py`` and keep the capture: how
``benchmarks/tests/data/small_tpu.xplane.pb`` was recorded.

    python3 benchmarks/tools/trace_probe.py <output directory>
"""

import json
import os
import shutil
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp

from readers import xplane


@jax.jit
def probe_step(x):
    return jnp.tanh(x @ x).sum()


def main(out_dir: str) -> None:
    os.makedirs(out_dir, exist_ok=True)
    x = jnp.ones((1024, 1024), jnp.float32)
    probe_step(x).block_until_ready()
    trace_dir = os.path.join(out_dir, "capture")
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 1
    jax.profiler.start_trace(trace_dir, profiler_options=options)
    with jax.profiler.TraceAnnotation(xplane.MARKER, wall=time.time()):
        pass
    start = time.time()
    for _ in range(3):
        probe_step(x).block_until_ready()
        time.sleep(0.05)
    end = time.time()
    jax.profiler.stop_trace()
    path = xplane.find_capture(trace_dir)
    from jax.profiler import ProfileData

    listing = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            events = list(line.events)
            listing.append({
                "plane": plane.name, "line": line.name, "events": len(events),
                "first": [[e.name, e.start_ns, e.duration_ns] for e in events[:4]],
            })
    reduced = xplane.reduce(
        path, "/device:TPU:" if jax.default_backend() == "tpu" else "/host:CPU"
    )
    summary = None
    try:
        summary = xplane.summarise(reduced, start, end)
        summary["gaps"] = summary["gaps"][:5]
    except ValueError as error:
        summary = {"error": str(error)}
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    shutil.rmtree(trace_dir)
    with open(os.path.join(out_dir, "probe.json"), "w") as handle:
        json.dump({
            "device": [jax.default_backend(), jax.devices()[0].device_kind],
            "bytes": os.path.getsize(os.path.join(out_dir, "small.xplane.pb")),
            "window": [start, end], "wall_at_zero": reduced["wall_at_zero"],
            "lines": listing, "summary": summary,
        }, handle, indent=1, default=str)


if __name__ == "__main__":
    main(sys.argv[1])
