"""Bytes the engine counts as moved by preemption and resume
(``preempt_bytes`` + ``resume_bytes``: the 4-bit latent codes and scales of
the tokens not covered by the shadow) per output token of the traced
window, in KiB."""


def read(facts, red, peaks):
    if not facts["tokens"]:
        return None
    return (facts["preempt_bytes"] + facts["resume_bytes"]) \
        / facts["tokens"] / 1024.0
