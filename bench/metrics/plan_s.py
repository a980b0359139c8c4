"""Plan search and prepare: the harness's clock around
``gather_statistics`` + ``choose_plan`` + ``prepare`` (the session build
in the screening cell), in seconds."""


def read(rec):
    return rec["clock"].get("plan_s")
