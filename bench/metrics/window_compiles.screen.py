"""Backend compiles inside the window (``jax.monitoring``'s
``backend_compile_duration`` events): each is a stall that set-up's
warm-up should have taken. 0 is a reading."""


def read(rec):
    return rec["counters"].get("window")
