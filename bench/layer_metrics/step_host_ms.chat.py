"""Host time of the engine per program dispatched over the window: its
serving.* spans (ServingEngine._span) but serving.sync, the wait on the
device (engine counters host_ns and dispatches).  In a step that returns
its tokens the device idles for this time."""


def read(ctx):
    n = ctx.stats.get("dispatches")
    return ctx.stats["host_ns"] / n / 1e6 if n else None
