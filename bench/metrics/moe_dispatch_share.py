"""Kernels: the share of the decode tick's expert layer spent outside the
expert matmuls: device time of ``jit_tick``'s ``moe/router``,
``moe/dispatch`` and ``moe/combine`` scopes over all of its ``moe``
scopes, in %."""
PROGRAM = "jit_tick"
AROUND = ("moe/router", "moe/dispatch", "moe/combine")


def _under(scope: str, name: str) -> bool:
    return scope == name or scope.startswith(name + "/")


def read(record):
    dev = record["device"]
    if not dev:
        return None
    moe = {s: t for s, t in dev["scopes"].get(PROGRAM, {}).items()
           if _under(s, "moe")}
    total = sum(moe.values())
    if not total:
        return None
    around = sum(t for s, t in moe.items()
                 if any(_under(s, name) for name in AROUND))
    return 100.0 * around / total
