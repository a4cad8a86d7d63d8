"""Useful work of a step, from its real sampled counts: model FLOPs per
configuration's model (``<model>.py``) and the operations and bytes of
single kernels (``kernels.py``)."""
import importlib.util
import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def load_model(name: str):
    """``<name>.py``: ``step_flops(config, counts)`` and the kernel calls
    of one step (``spmm_calls``, ``edge_softmax_calls``) where it has
    them. ``counts`` lists the blocks, outermost first, as dicts of real
    ``seeds``, ``next`` and ``edges`` counts."""
    path = os.path.join(_HERE, f"{name}.py")
    if not os.path.exists(path):
        raise ValueError(f"no work function for model {name!r} in {_HERE}")
    spec = importlib.util.spec_from_file_location(f"work_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """The chip's published peaks; a kind not in ``peaks.json`` is an
    error."""
    with open(os.path.join(os.path.dirname(_HERE), "peaks.json")) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"peaks.json (listed: {sorted(table)})")
    return table[device_kind]
