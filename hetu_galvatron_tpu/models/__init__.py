from hetu_galvatron_tpu.models.builder import (  # noqa: F401
    MODULE_REGISTRY,
    build_causal_lm_arch,
    causal_lm_loss,
    forward_causal_lm,
    init_causal_lm,
    param_count,
)
