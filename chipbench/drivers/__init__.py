"""The drivers: one module per kind of loop, named by a traffic file's
``driver``."""


def llama_config(cfg: dict, dims: dict, dtype):
    """The program's ``LlamaConfig`` for a configuration file."""
    from horovod_tpu.models import llama
    return llama.LlamaConfig(
        vocab_size=dims["vocab_size"], d_model=dims["d_model"],
        n_layers=dims["n_layers"], n_heads=dims["n_heads"],
        n_kv_heads=dims["n_kv_heads"], d_ff=dims["d_ff"],
        rope_theta=dims["rope_theta"], dtype=dtype,
        **cfg.get("llama_config", {}))
