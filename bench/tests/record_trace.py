"""Record the small TPU trace that tests/test_bench_trace.py reduces.

    python3 bench/tests/record_trace.py <out_dir>

On one TPU chip: two compiled programs (a matrix product and an
elementwise pass), run a few times inside a host annotation named
``bench.window``, with a host sleep between them so the device idles.
Writes ``<out_dir>/small.xplane.pb`` and prints the planes, lines and a
few events of each line.
"""

from __future__ import annotations

import glob
import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp


def main(out_dir: str) -> None:
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_trace: needs a TPU")
    mm = jax.jit(lambda a: (a @ a).sum())
    ew = jax.jit(lambda a: jnp.tanh(a) * 2.0 + 1.0)
    a = jnp.ones((2048, 2048), jnp.bfloat16)
    mm(a).block_until_ready()
    ew(a).block_until_ready()
    log_dir = os.path.join(out_dir, "log")
    shutil.rmtree(log_dir, ignore_errors=True)
    jax.profiler.start_trace(log_dir)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            mm(a).block_until_ready()
            time.sleep(0.002)
            ew(a).block_until_ready()
        x = mm(a)
        ew(a).block_until_ready()           # two programs in flight
        x.block_until_ready()
    jax.profiler.stop_trace()
    path = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                     recursive=True)[0]
    shutil.copy(path, os.path.join(out_dir, "small.xplane.pb"))
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(path).planes:
        print("plane", plane.name)
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs))
            for e in evs[:4]:
                print("    ", repr(e.name), e.start_ns, e.duration_ns,
                      [(k, v) for k, v in e.stats][:6])


if __name__ == "__main__":
    main(sys.argv[1])
