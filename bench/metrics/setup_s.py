"""Process start to window open: imports, weights, engine start with its
warm compile, and the mix's warm-up traffic (host clock)."""


def read(rec):
    return rec["setup_s"]
