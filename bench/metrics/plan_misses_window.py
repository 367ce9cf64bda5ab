"""Plan-registry misses plus JAX compilations (backend compiles and
persistent-cache loads) inside the window.  Every shape is warmed in set-up,
so it should read 0."""


def read(run):
    return float(run.counters["plan_misses"] + run.counters["compiles"])
