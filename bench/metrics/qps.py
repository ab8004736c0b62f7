"""qps: requests answered inside the window per second of the window, by
the host clock."""


def read(run):
    w = run.window
    done = sum(1 for r in w.requests if r.done is not None and r.done <= w.t1)
    return done / w.seconds
