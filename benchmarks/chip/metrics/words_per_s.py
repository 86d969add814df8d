"""words_per_s: payload Z_q words served in the window, over its seconds
(host clock).  A request that overlaps the window's edge is credited for
the share of its time from due to reply that lies inside the window, so
the work of a closed loop's last round counts whether its replies land
just before the close or just after it."""


def read(ctx):
    words = 0.0
    for s in ctx.sent:
        if not s.ok:
            continue
        inside = min(s.done, ctx.t_end) - max(s.due, ctx.t0)
        if inside > 0:
            words += s.req.words * inside / max(s.done - s.due, 1e-9)
    return words / ctx.seconds
