"""Launch layer: host mesh, train/serve steps, drivers."""
