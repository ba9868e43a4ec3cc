"""Faults planted in the timed path, for the checks' own tests and for the
readings that set the limits: each breaks one thing the check must see.

- ``state_unchanged``: the client step returns its state as it came;
- ``half_batch``: the client step takes the mean over half of its batch;
- ``answer_altered``: the cohort eval scores misses instead of hits;
- ``token_altered``: the decode step's logits are shifted by one token.

Each is a context manager that patches the program's module attribute the
driver looks up when it builds the path, and restores it after.
"""
from __future__ import annotations

import contextlib

FAULTS = ("state_unchanged", "half_batch", "answer_altered", "token_altered")


@contextlib.contextmanager
def _patched(module, name, wrap):
    orig = getattr(module, name)
    setattr(module, name, wrap(orig))
    try:
        yield
    finally:
        setattr(module, name, orig)


def _client_fns(step=None, evaluate=None):
    def wrap(orig):
        def patched(*a, **k):
            local_step, eval_client = orig(*a, **k)
            return (step(local_step) if step else local_step,
                    evaluate(eval_client) if evaluate else eval_client)
        return patched
    return wrap


def _state_unchanged(fn):
    def broken(frozen, trainable, opt_state, batch):
        _, _, loss = fn(frozen, trainable, opt_state, batch)
        return trainable, opt_state, loss
    return broken


def _half_batch(fn):
    def broken(frozen, trainable, opt_state, batch):
        half = {k: v[: v.shape[0] // 2] for k, v in batch.items()}
        return fn(frozen, trainable, opt_state, half)
    return broken


def _answer_altered(fn):
    def broken(*args):
        corr, cnt = fn(*args)
        return cnt - corr, cnt
    return broken


def _token_altered(orig):
    import jax.numpy as jnp

    def patched(*a, **k):
        fn = orig(*a, **k)

        def broken(params, cache, tokens, lora=None):
            logits, cache = fn(params, cache, tokens, lora)
            return jnp.roll(logits, 1, axis=-1), cache
        return broken
    return patched


def plant(fault: str):
    """Context manager planting ``fault`` in the program."""
    if fault == "token_altered":
        import repro.launch.steps as steps
        return _patched(steps, "make_serve_step", _token_altered)
    import repro.core.pftt as pftt
    wraps = {"state_unchanged": _client_fns(step=_state_unchanged),
             "half_batch": _client_fns(step=_half_batch),
             "answer_altered": _client_fns(evaluate=_answer_altered)}
    return _patched(pftt, "_client_fns", wraps[fault])
