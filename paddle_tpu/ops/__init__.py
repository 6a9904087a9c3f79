from . import collectives
from .device_graph import DeviceGraph

__all__ = ["collectives", "DeviceGraph"]
