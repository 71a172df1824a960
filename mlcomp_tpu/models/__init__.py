"""Model zoo registry.

The reference's model surface comes from Catalyst/torchvision (ResNet-50
classification, U-Net segmentation, BERT finetune — BASELINE.json:7-11);
here each family is a flax.linen module designed for the MXU: bfloat16
activations, channel sizes padded to hardware tiles where it matters, and
no Python-dynamic control flow under jit.
"""

from mlcomp_tpu.utils.registry import Registry

MODELS: Registry = Registry("models")


def load_all() -> None:
    """Import every model module for registration side effects."""
    from mlcomp_tpu.models import mlp as _mlp  # noqa: F401
    from mlcomp_tpu.models import cnn as _cnn  # noqa: F401

    import importlib

    for mod in ("resnet", "unet", "bert", "transformer", "moe", "vit", "pipeline_lm",
                "mixed_layer_lm"):
        name = f"mlcomp_tpu.models.{mod}"
        try:
            importlib.import_module(name)
        except ModuleNotFoundError as e:
            if e.name != name:
                raise


def create_model(cfg):
    """Build a model from ``{name: ..., **kwargs}`` config."""
    load_all()
    cfg = dict(cfg)
    name = cfg.pop("name")
    return MODELS.create(name, **cfg)
