from .kernel import TILE, table_bytes, vmem_gather

__all__ = ["TILE", "table_bytes", "vmem_gather"]
