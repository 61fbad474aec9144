"""Records ``scoped_v5e.xplane.pb``, the trace ``test_spans.py`` reads.

A jitted ``_update`` whose ops sit under two ``cpapr.*`` scopes runs once
inside a host span ``cpapr.solve`` that carries attributes, one given as
it opens and two set as it ends, all inside ``bench.window``.  Run it on
a TPU, from the root of the repository:

    python3 bench/tests/data/scoped_v5e.py bench/tests/data
"""
import glob
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp


def _update(x):
    with jax.named_scope("cpapr.pi"):
        y = jnp.sort(x, axis=0)  # a sort fuses with nothing
    with jax.named_scope("cpapr.phi"):
        return jnp.sum(y @ x)


def main(out: str) -> None:
    f = jax.jit(_update)
    x = jnp.arange(256 * 256, dtype=jnp.float32).reshape(256, 256) % 7
    f(x).block_until_ready()  # compiled before the trace
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        with jax.profiler.TraceAnnotation("cpapr.solve", modes=4) as span:
            f(x).block_until_ready()
            span.set_metadata(sweeps=5, host_syncs=55)
    jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp}/plugins/profile/*/*.xplane.pb")
    shutil.copy(path, f"{out}/scoped_v5e.xplane.pb")
    print(f"device {jax.devices()[0].device_kind}: wrote "
          f"{out}/scoped_v5e.xplane.pb")


if __name__ == "__main__":
    main(sys.argv[1])
