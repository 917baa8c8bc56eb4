"""Device milliseconds a step under no name of ``DEVICE_SCOPES``: what the
program's scopes do not reach. With ``blocks.attention_ms_per_step``,
``blocks.ffn_ms_per_step``, ``blocks.head_loss_ms_per_step``,
``trainer.optimizer_ms_per_step`` and the time under ``embed`` (which has no
metric of its own) it sums to the device's busy time per step."""

import pb_spans


def read(facts):
    return pb_spans.scope_ms_per_step(facts, None)
