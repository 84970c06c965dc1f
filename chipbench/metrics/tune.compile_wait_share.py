"""tune.compile_wait_share: percent of the searches' wall time in which the
engine's serial loop waited on compiles (``EngineStats.compile_wait_s`` over
``EngineStats.wall_s``, each summed over the window's searches)."""


def read(run):
    wall = run.engine.get("wall_s", 0.0)
    if not wall:
        return None
    return 100.0 * run.engine.get("compile_wait_s", 0.0) / wall
