from repro.models.transformer import (  # noqa: F401
    decode_step,
    forward,
    init_cache,
    init_params,
    prefill,
    random_checkpoint,
)
