"""Process start to window start: JAX start-up, weights from the seed,
engine construction and tuning, compiles (or compile-cache reads), and
the warm-up traffic."""


def read(rec):
    return rec.setup_s
