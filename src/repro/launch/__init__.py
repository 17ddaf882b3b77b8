from repro.launch.mesh import make_mesh, make_mesh_for

__all__ = ["make_mesh", "make_mesh_for"]
