"""A stand-in for the reference module of a configuration that counts its
own FLOPs (as one whose blocks ``chipbench.counts`` does not describe
would): the transformer reference, with ``train_flops_per_token`` of its
own, 1000 FLOPs per token and position."""

from chipbench.reference.transformer import *  # noqa: F401,F403


def train_flops_per_token(model: dict, seq: int) -> float:
    return 1000.0 * seq
