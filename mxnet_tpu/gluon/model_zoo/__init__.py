"""Predefined models (reference ``python/mxnet/gluon/model_zoo/``)."""
from . import bert, glm4_moe_lite, nemotron_h, olmo_hybrid, vision
from .bert import BERTModel, bert_base, bert_small
from .vision import get_model
