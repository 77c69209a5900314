"""Plain float32 references, one module per benchmark configuration."""
