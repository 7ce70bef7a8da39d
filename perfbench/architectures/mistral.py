"""`model_type` "mistral": a dense decoder, everything from `dense`."""

from perfbench.architectures.dense import (  # noqa: F401
    logits_for,
    make_weights,
    weight_bytes,
    work,
)
