"""Record the small profiler trace that ``test_trace_reduce.py`` reads.

Run on a machine with a TPU, from the root of a checkout:

    python3 bench/tests/record_trace.py [OUT_DIR]

It traces three steps of a small jitted program, each followed by a host
wait inside a ``TraceAnnotation``, so the trace holds device ops, idle
gaps and the host span that explains them.  The ``.xplane.pb`` goes to
``OUT_DIR`` (default ``trace_out/``); a structural summary of every
plane, line and the first events with their stats goes to standard
output.  The committed copy is ``bench/tests/data/tiny.xplane.pb``.
"""

import glob
import os
import shutil
import sys
import tempfile
import time


def summarize(path, max_events=6):
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    for plane in pd.planes:
        lines = list(plane.lines)
        print(f"plane {plane.name!r}: {len(lines)} lines")
        for line in lines:
            evs = list(line.events)
            print(f"  line {line.name!r}: {len(evs)} events")
            for ev in evs[:max_events]:
                stats = {k: (str(v)[:160]) for k, v in ev.stats}
                print(f"    {ev.name!r} start={ev.start_ns:.0f} "
                      f"dur={ev.duration_ns:.0f} stats={stats}")


def main():
    out_dir = sys.argv[1] if len(sys.argv) > 1 else "trace_out"
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU found", file=sys.stderr)
        return 1

    @jax.jit
    def step(a, b):
        with jax.named_scope("bench_matmul"):
            c = a @ b
        with jax.named_scope("bench_scale"):
            return jnp.tanh(c) * 0.5

    a = jnp.ones((2048, 2048), jnp.bfloat16)
    b = jnp.ones((2048, 2048), jnp.bfloat16)
    jax.block_until_ready(step(a, b))
    tmp = tempfile.mkdtemp(prefix="record_trace_")
    try:
        jax.profiler.start_trace(tmp)
        for i in range(3):
            with jax.profiler.TraceAnnotation("bench/tick"):
                a = jax.block_until_ready(step(a, b))
            with jax.profiler.TraceAnnotation("bench/host_wait"):
                time.sleep(0.02)
        jax.profiler.stop_trace()
        path = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                         recursive=True)[0]
        os.makedirs(out_dir, exist_ok=True)
        dest = os.path.join(out_dir, "tiny.xplane.pb")
        shutil.copy(path, dest)
        print(f"wrote {dest} ({os.path.getsize(dest)} bytes)")
        summarize(dest)
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
