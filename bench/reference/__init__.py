"""Plain float32 references, written from the equations."""
