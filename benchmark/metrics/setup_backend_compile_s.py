"""Seconds jax spent lowering and compiling (or reading from the
persistent cache) in set-up, every program: the program's
``jit_lower_s/<fun>`` and ``jit_backend_compile_s/<fun>`` stage totals."""
from benchmark.harness import program_obs


def read(run):
    spent = {**program_obs.stage_totals("jit_lower_s/"),
             **program_obs.stage_totals("jit_backend_compile_s/")}
    if not spent:
        return None
    largest = sorted(spent.items(), key=lambda kv: -kv[1])[:8]
    print("compiles: %s" % [[k, round(v, 3)] for k, v in largest],
          flush=True)
    return sum(spent.values())
