#!/usr/bin/env python3
"""Record ``small.xplane.pb``, the trace the reduction's tests read, on a
TPU: two programs named as the serving path names its own (``fused``, a
loop of matmuls, and ``prefill_chunk``), a 50 ms host sleep between them
under the span ``host.sleep``, all inside the span ``bench.window``.

    python3 bench/tests/fixtures/record_trace.py [out_dir]

Prints the trace's planes, lines and a few events of each, and the
reduction of it.
"""
import glob
import shutil
import sys
import tempfile
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE.parents[1]))


def main(out: Path) -> int:
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    from harness import xtrace

    if jax.devices()[0].platform != "tpu":
        print("record_trace: no TPU", file=sys.stderr)
        return 1

    @jax.jit
    def fused(x):
        return jax.lax.fori_loop(0, 8, lambda i, a: jnp.tanh(a @ a), x)

    @jax.jit
    def prefill_chunk(x):
        return jnp.sin(x @ x.T).sum(0)

    x = jnp.ones((1024, 1024), jnp.bfloat16) * 0.01
    fused(x).block_until_ready()
    prefill_chunk(x).block_until_ready()
    tmp = tempfile.mkdtemp()
    jax.profiler.start_trace(tmp)
    with jax.profiler.TraceAnnotation("bench.window"):
        fused(x).block_until_ready()
        with jax.profiler.TraceAnnotation("host.sleep"):
            time.sleep(0.05)
        prefill_chunk(x).block_until_ready()
        fused(x).block_until_ready()
    jax.profiler.stop_trace()
    src = glob.glob(f"{tmp}/**/*.xplane.pb", recursive=True)[0]
    out.mkdir(parents=True, exist_ok=True)
    shutil.copy(src, out / "small.xplane.pb")
    pd = ProfileData.from_file(str(out / "small.xplane.pb"))
    for plane in pd.planes:
        print("plane", repr(plane.name))
        for line in plane.lines:
            evs = list(line.events)
            print("  line", repr(line.name), len(evs))
            for e in evs[:4]:
                print("    event", repr(e.name), e.start_ns, e.duration_ns,
                      {k: v for k, v in e.stats})
    print(xtrace.reduce(str(out / "small.xplane.pb"), "bench.window"))
    return 0


if __name__ == "__main__":
    sys.exit(main(Path(sys.argv[1]) if len(sys.argv) > 1 else HERE))
