"""window_occupancy_pct: 100 x blocks of the window's answered requests /
(windows the tenants served over the window, from the stats op before
and after it, x lanes per window).  Nothing else is in flight at either
snapshot, so each counts exactly the window's work."""


def read(ctx):
    windows = ctx.windows_served()
    if windows <= 0:
        return None
    return 100.0 * ctx.blocks_served() / (windows * ctx.window)
