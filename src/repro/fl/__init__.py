"""Federated-learning runtime: channels, engine, registry, tasks, data."""
from . import channels, data, engine, nets, registry, tasks  # noqa: F401
