from .nets import SimpleConvNet, GeeseNet, GeisterNet
from .transformer import TransformerNet
from .hybrid import HybridNet
from .inference import (
    InferenceModel,
    RandomModel,
    build_inference_model,
    fetch_outputs,
    init_variables,
)
from .export import ExportedModel, OnnxModel, export_model, export_onnx

__all__ = [
    "SimpleConvNet",
    "GeeseNet",
    "GeisterNet",
    "TransformerNet",
    "HybridNet",
    "InferenceModel",
    "RandomModel",
    "build_inference_model",
    "fetch_outputs",
    "init_variables",
    "ExportedModel",
    "OnnxModel",
    "export_model",
    "export_onnx",
]
