"""``tests/conftest.py`` gives back what a worker compiled: the executables
JAX's in-process caches hold are hundreds of memory mappings a program, and
a process that reaches ``vm.max_map_count`` dies inside its next compile."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from conftest import _mappings, shed_compiled_programs


def _a_dozen_programs(first: int):
    return [jax.jit(lambda a, k=k: jnp.tanh(a @ a + k).sum(0))
            for k in range(first, first + 12)]


def test_shedding_gives_back_the_mappings_of_what_was_compiled():
    x = jnp.arange(64.0).reshape(8, 8)
    # once before anything is counted: what the first compiles of a process
    # map for good (thread pools, arenas) is not an executable's
    for f in _a_dozen_programs(100):
        f(x)
    shed_compiled_programs()
    before = _mappings()
    assert before > 0                       # /proc is there to be read
    programs = _a_dozen_programs(0)
    results = [float(f(x)[0]) for f in programs]
    held = _mappings()
    assert held > before + 12, (before, held)
    shed_compiled_programs()
    assert _mappings() <= before + 4, (before, held, _mappings())
    # what survives the shedding compiles again and gives what it gave
    assert [float(f(x)[0]) for f in programs] == results
