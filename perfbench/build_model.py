"""A configuration file's ``preset`` -> the program's model object."""


def build_model(preset: dict, **extra):
    """``{"family", "size"}`` goes through ``models/presets.py get_model``
    (every cell); ``{"transformer_config": {...}}`` builds the program's
    ``TransformerConfig`` directly (the rehearsal toy, whose sizes no preset
    has)."""
    if "transformer_config" in preset:
        from deepspeed_tpu.models import CausalLM
        from deepspeed_tpu.models.transformer import TransformerConfig
        return CausalLM(TransformerConfig(**preset["transformer_config"], **extra))
    from deepspeed_tpu.models.presets import get_model
    return get_model(preset["family"], preset.get("size"), **extra)
