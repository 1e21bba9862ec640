from dalle_tpu.models.dalle import DALLE, init_params, param_count  # noqa: F401
from dalle_tpu.models.transformer import Transformer, TransformerBlock  # noqa: F401


def family(cfg):
    """The module of ``cfg``'s architecture (``cfg.model_module``): it
    builds the model (``build(cfg, mesh)``), initialises its parameters
    (``init_params(model, rng)``), says its own engagement records
    (``engagement_records(cfg, mesh)``: attributes of the ``setup/warmup``
    row) and names the entries of the step's ``aux`` that go onto every
    ``loop/step`` row (``step_attributes(cfg)``; ``SLOW_STEP_ATTRIBUTES``
    are those that count a slower lowering)."""
    import importlib
    return importlib.import_module(cfg.model_module)
