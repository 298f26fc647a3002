from polyaxon_tpu.utils.env import cpu_mesh_xla_flags

__all__ = ["cpu_mesh_xla_flags"]
