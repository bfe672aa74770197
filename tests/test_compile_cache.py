"""Where the persistent compilation cache lands: in JAX_COMPILATION_CACHE_DIR
when it is set, else in <checkout>/.jax_cache, and a second run hits it.
Each run is its own process, as an entry point's is."""
import os
import subprocess
import sys

import pytest

from repro.runtime.compile_cache import DEFAULT_DIR, ENV

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# one small program, compiled in a fresh process; prints the cache directory
# and how many cache hits the compile made
PROGRAM = """
import collections, jax, jax.numpy as jnp
from repro.runtime.compile_cache import enable_compile_cache
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
events = collections.Counter()
jax.monitoring.register_event_listener(lambda name, **kw: events.update([name]))
path = enable_compile_cache()
jax.jit(lambda x: jnp.cumsum(x * 3 + {tag})).lower(jnp.arange(8.0)).compile()
print(path, events["/jax/compilation_cache/cache_hits"])
"""


def _run(tag: str, cache_env):
    env = {**os.environ, "PYTHONPATH": os.path.join(REPO, "src"),
           "JAX_PLATFORMS": "cpu"}
    env.pop(ENV, None)
    if cache_env:
        env[ENV] = cache_env
    out = subprocess.run([sys.executable, "-c", PROGRAM.format(tag=tag)],
                         env=env, capture_output=True, text=True, timeout=120,
                         cwd=REPO)
    assert out.returncode == 0, out.stderr[-2000:]
    path, hits = out.stdout.split()[-2:]
    return path, int(hits)


def _entries(path) -> set:
    return set(os.listdir(path)) if os.path.isdir(path) else set()


@pytest.mark.parametrize("placed", [True, False])
def test_cache_lands_in_its_directory_and_hits(tmp_path, placed):
    target = str(tmp_path / "cache") if placed else str(DEFAULT_DIR)
    default_before = _entries(DEFAULT_DIR)
    path, hits = _run(f"{placed:d}.5", target if placed else None)
    assert path == target
    if placed:       # a new entry there, and nothing in the default dir
        assert _entries(target) and _entries(DEFAULT_DIR) == default_before
    else:            # a new entry, or one an earlier run left and hit
        assert _entries(target) - default_before or hits >= 1
    _, hits = _run(f"{placed:d}.5", target if placed else None)
    assert hits >= 1
