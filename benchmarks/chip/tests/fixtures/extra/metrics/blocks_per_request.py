"""blocks_per_request: mean blocks of the window's answered requests (a
reader that only this fixture directory has)."""


def read(ctx):
    ok = [s.req.blocks for s in ctx.sent if s.ok]
    return sum(ok) / len(ok) if ok else None
