"""Device time of the latent-attention operator (``self_attn``) less its
custom calls, per traced step and chip, every phase, in milliseconds: the
five projections (``q_proj``, ``kv_a_proj_with_mqa``, ``kv_b_proj``,
``o_proj`` and their gradients), ``kv_a_layernorm``, the rotary embedding
(``ds.rope``) and the splits, the rope key's broadcast over the heads and
the concatenations (``ds.mla.assemble``). The custom calls are the
``%mla_*`` kernels, which ``mla.kernel_ms_per_step`` reads. Only where the
configuration is a latent one: the same sum in another cell is
``scope.mixer_proj_ms_per_step``."""

from benchmark import scope_time


def read(run):
    if "kv_lora_rank" not in run.get("config", {}):
        return None
    return scope_time.part_ms(run, "mixer", less_custom=True)
