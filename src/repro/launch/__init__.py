"""Device-mesh construction."""
