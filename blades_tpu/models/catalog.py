"""Model catalog (ref: fllib/models/catalog.py:16-47).

Resolves a model spec — substring-matched name ("cct"/"resnet"/"mlp"/"cnn",
same matching rule as the reference), a flax Module instance, a custom
registered name, or a dict ``{"type": name, **builder keywords}`` (how a
YAML states a model that has a configuration of its own, e.g.
``mla_moe_lm``, ``gqa_moe_lm``, ``looped_lm``) — to a linen module.
"""

from __future__ import annotations

from typing import Callable, Dict

import flax.linen as nn

from blades_tpu.models.cct import VARIANTS as _CCT_VARIANTS
from blades_tpu.models.cct import cct_2_3x2_32
from blades_tpu.models.cnn import FashionCNN
from blades_tpu.models.gqa_moe import gqa_moe_lm
from blades_tpu.models.looped_lm import looped_lm
from blades_tpu.models.mla_moe import mla_moe_lm
from blades_tpu.models.mlp import MLP
from blades_tpu.models.resnet import (
    ResNet10,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)

_CUSTOM: Dict[str, Callable[..., nn.Module]] = {}

# Models built from keywords (a dict spec's keys beside "type").
_CONFIGURED = {"mla_moe_lm": mla_moe_lm, "gqa_moe_lm": gqa_moe_lm,
               "looped_lm": looped_lm}

_RESNETS = {
    "resnet10": ResNet10,
    "resnet18": ResNet18,
    "resnet34": ResNet34,
    "resnet50": ResNet50,
    "resnet101": ResNet101,
    "resnet152": ResNet152,
}


def register_model(name: str, builder: Callable[..., nn.Module]) -> None:
    """Register a custom model builder (ref: catalog.py:37-47)."""
    _CUSTOM[name.lower()] = builder


class ModelCatalog:
    @staticmethod
    def get_model(spec, num_classes=None) -> nn.Module:
        """Resolve ``spec`` to a linen module.

        ``num_classes=None`` keeps each builder's own default — so presets
        that carry a class count in the name (e.g. ``cct_7_3x1_32_c100``
        defaults to 100) are not silently overridden to 10.
        """
        if isinstance(spec, nn.Module):
            return spec
        if callable(spec) and not isinstance(spec, str):
            return spec()
        kw = {} if num_classes is None else {"num_classes": num_classes}
        if isinstance(spec, dict):
            kw.update({k: v for k, v in spec.items() if k != "type"})
            spec = spec["type"]
        name = str(spec).lower()
        if name in _CONFIGURED:
            return _CONFIGURED[name](**kw)
        if name in _CUSTOM:
            return _CUSTOM[name](**kw)
        if name in _RESNETS:
            return _RESNETS[name](**kw)
        if name in _CCT_VARIANTS:
            return _CCT_VARIANTS[name](**kw)
        # Substring matching, same precedence as the reference
        # (ref: fllib/models/catalog.py:16-29): "resnet" -> ResNet10.
        if "cct" in name:
            return cct_2_3x2_32(**kw)
        if "resnet" in name:
            return ResNet10(**kw)
        if "mlp" in name:
            return MLP(**kw)
        if "cnn" in name:
            return FashionCNN(**kw)
        raise KeyError(f"unknown model {spec!r}")
