"""Output tokens whose ledger stamp falls inside the window, over the
window's seconds."""


def read(run):
    w = run.window
    n = sum(w.t0 <= t < w.t1 for r in w.requests for t in w.stamps(r))
    return n / w.seconds
