"""Faults planted under the timed path, to show that the correctness check
catches them.  Each takes what a driver's ``fault=`` hook is given (the
serve engine, or the train step function) and breaks it in one way.
Used by ``bench/tests`` on the CPU and ``bench/tools/controls.py`` on the
chip; never by a benchmark run."""

from __future__ import annotations

import contextlib


def token_altered(engine) -> None:
    """Every token the engine samples comes out as the next id."""
    import jax.numpy as jnp

    vocab = engine.cfg.vocab_size
    sample = engine._sample

    def altered(*a):
        return (jnp.asarray(sample(*a)) + 1) % vocab
    engine._sample = altered


def state_unchanged(step_fn):
    """The step runs, but hands back the parameters it was given."""
    def f(params, *rest):
        out = step_fn(params, *rest)
        return (params,) + tuple(out[1:])
    return f


def half_batch(step_fn):
    """Half of the batch's rows are left out; the mean is over the rest."""
    def f(*args):
        *state, batch = args
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return step_fn(*state, half)
    return f


@contextlib.contextmanager
def _local_reduce_scatter():
    from jax import lax
    from repro.core import collectives as C

    real = C.reduce_scatter

    def local(x, schedule, axis_names, sizes, codec=None):
        world = 1
        for s in sizes:
            world *= s
        n = x.shape[0] // world
        rev = C.bit_reversed_index(axis_names, sizes)
        return lax.dynamic_slice_in_dim(x, rev * n, n)

    C.reduce_scatter = local
    try:
        yield
    finally:
        C.reduce_scatter = real


def exchange_left_out(step_fn):
    """The gradient's reduce-scatter sends nothing: each rank keeps its
    own slice of its own gradient (traced on the first call)."""
    def f(*args):
        with _local_reduce_scatter():
            return step_fn(*args)
    return f


SERVE = {"token_altered": token_altered}
TRAIN = {"state_unchanged": state_unchanged, "half_batch": half_batch,
         "exchange_left_out": exchange_left_out}
