"""Programs launched on the first device per outer iteration (profiler
trace, every program, not ``jit_fused*`` alone; layer: game_descent)."""


def read(obs):
    work = obs.counters.get("work")
    return obs.trace.program_launches() / work if work else None
