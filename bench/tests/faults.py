"""Faults planted underneath a run's timed path, each of which a sound
comparison has to catch: a step that returns its cache unchanged, half of
the batch left out (its rows given the other half's logits), and a token
altered where it is produced (one row's token in every step, a different
row each step).  Each takes the engine and patches it in place."""
from __future__ import annotations

import dataclasses

import jax.numpy as jnp


def state_unchanged(eng):
    fwd = eng.model.forward_routed

    def step(params, batch, cache, *a, **k):
        logits, _, routing = fwd(params, batch, cache, *a, **k)
        return logits, cache, routing
    eng.model = dataclasses.replace(eng.model, forward_routed=step)


def half_batch(eng):
    fwd = eng.model.forward_routed

    def step(params, batch, cache, *a, **k):
        logits, cache, routing = fwd(params, batch, cache, *a, **k)
        h = logits.shape[0] // 2
        return (jnp.concatenate([logits[:h], logits[:logits.shape[0] - h]]),
                cache, routing)
    eng.model = dataclasses.replace(eng.model, forward_routed=step)


def token_altered(eng):
    sample = eng._sample_next
    vocab = eng.cfg.vocab_size

    def altered(logits, temps, topks, step_idx, sampling):
        tok = sample(logits, temps, topks, step_idx, sampling)
        row = step_idx % tok.shape[0]
        return tok.at[row].set((tok[row] + 1) % vocab)
    eng._sample_next = altered


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}


def planted(run_module, fault):
    """A ``new_driver`` for ``run_module`` that plants ``fault``."""
    make = run_module.new_driver

    def faulty(cell, b):
        d = make(cell, b)
        fault(d.engine)
        return d
    return faulty
