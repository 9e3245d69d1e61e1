"""What both trainer adapters share: the loss and the optimizer named by
the traffic file."""

from __future__ import annotations

import optax


def next_token_loss(logits, y):
    return optax.softmax_cross_entropy_with_integer_labels(
        logits[:, :-1], y[:, 1:]
    ).mean()


def optimizer(traffic: dict):
    opt = dict(traffic["optimizer"])
    return getattr(optax, opt.pop("name"))(**opt)
