"""Plain float32 references, independent of `ray_tpu/models`."""
