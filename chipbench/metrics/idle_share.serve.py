"""Share of the traced window in which no operation runs on the chips."""


def read(m):
    t = m["trace"]
    return 100.0 * (1.0 - t.busy_s() / t.window_s)
