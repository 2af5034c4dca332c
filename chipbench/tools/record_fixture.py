"""Record a small profiler trace on the chip for the trace-reduction test.

  python chipbench/tools/record_fixture.py <out_dir>

Traces a jitted matmul, an elementwise op and one fleet Pallas kernel
under named host spans, writes the ``.xplane.pb`` under ``<out_dir>`` and
prints the planes, lines and a few event names of each.
"""
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path.insert(0, str(ROOT / "src"))


def main(out: str) -> int:
    import jax
    import jax.numpy as jnp
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("record_fixture: no TPU attached")
    from repro.kernels.fleet_attribute.kernel import fleet_attribute_kernel
    a = jnp.ones((1024, 1024), jnp.bfloat16)
    t = jnp.cumsum(jnp.full((64, 1024), 1e-3, jnp.float32), axis=1)
    e = jnp.cumsum(jnp.full((64, 1024), 0.2, jnp.float32), axis=1)
    w = jnp.zeros((64, 1), jnp.float32)
    ph = jnp.asarray([[0.1, 0.5], [0.5, 0.9]] + [[2.0, 2.0]] * 30,
                     jnp.float32)
    mm = jax.jit(lambda x: (x @ x).sum())
    ew = jax.jit(lambda x: jnp.tanh(x) * 2.0)
    ker = jax.jit(lambda t, e, w, p: fleet_attribute_kernel(t, e, w, p))
    jax.block_until_ready((mm(a), ew(a), ker(t, e, w, ph)))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(out, profiler_options=opts):
        for i in range(3):
            with jax.profiler.TraceAnnotation(f"bench.step{i}"):
                jax.block_until_ready(mm(a))
                jax.block_until_ready(ew(a))
                jax.block_until_ready(ker(t, e, w, ph))
            time.sleep(0.01)
    files = sorted(Path(out).rglob("*.xplane.pb"))
    print("files", [str(f) for f in files])
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(files[-1]))
    for plane in pd.planes:
        lines = list(plane.lines)
        print("PLANE", repr(plane.name), len(lines))
        for line in lines:
            evs = list(line.events)
            print("  LINE", repr(line.name), len(evs))
            for ev in evs[:6]:
                stats = {}
                try:
                    stats = {k: v for k, v in ev.stats}
                except Exception as exc:  # noqa: BLE001
                    stats = {"err": str(exc)}
                print("    EV", repr(ev.name), ev.start_ns, ev.duration_ns,
                      str(stats)[:300])
    return 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1]))
