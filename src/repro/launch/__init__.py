"""Launchers: production meshes, multi-pod dry-run, training driver, roofline.

NOTE: ``repro.launch.dryrun`` sets XLA_FLAGS at import; import it only in a
dedicated process (``python -m repro.launch.dryrun``).
"""

from .mesh import V5E, make_device_mesh, make_production_mesh

__all__ = ["V5E", "make_device_mesh", "make_production_mesh"]
