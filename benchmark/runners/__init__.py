"""Runners drive the program; this is the one place they build its model
configuration from a configuration file's sizes."""


def gpt_config(config: dict, **kw):
    """The program's GPTConfig at the file's sizes and dtype."""
    from paddle_tpu.models.gpt import GPTConfig
    s = config["sizes"]
    return GPTConfig(
        vocab_size=s["vocab_size"], hidden_size=s["hidden_size"],
        num_layers=s["num_layers"], num_heads=s["num_heads"],
        max_position_embeddings=s["max_position_embeddings"],
        intermediate_size=s["intermediate_size"],
        layer_norm_eps=s["layer_norm_eps"],
        initializer_range=s["initializer_range"],
        amp_dtype=config["dtype"], **kw)
