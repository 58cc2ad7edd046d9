"""Faults planted in the program underneath the timed path, to show that
``correct`` catches them (bench/tests and ``calibrate.py --fault``)."""
from __future__ import annotations

import contextlib
from unittest import mock


@contextlib.contextmanager
def state_unchanged():
    """The optimizer step returns parameters and state as they came."""
    from repro.optim import adamw

    def frozen(grads, state, params, cfg, lr):
        return params, state, adamw.global_norm(grads)

    with mock.patch.object(adamw, "update", frozen):
        yield


@contextlib.contextmanager
def half_batch():
    """The loss sees only the first half of the batch's rows, and takes
    its mean over them."""
    from repro.models import model as model_lib
    orig = model_lib.loss_fn

    def half(adapter, base, frozen, batch, *a, **k):
        n = batch["tokens"].shape[0] // 2
        return orig(adapter, base, frozen,
                    {key: v[:n] for key, v in batch.items()}, *a, **k)

    with mock.patch.object(model_lib, "loss_fn", half):
        yield


@contextlib.contextmanager
def token_altered():
    """Every sampled token is replaced by the next id where it is made."""
    from repro.serving import sampling
    orig = sampling.sample

    def altered(logits, *a, **k):
        return (orig(logits, *a, **k) + 1) % logits.shape[-1]

    with mock.patch.object(sampling, "sample", altered):
        yield


FAULTS = {"state_unchanged": state_unchanged, "half_batch": half_batch,
          "token_altered": token_altered}
