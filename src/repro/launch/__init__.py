"""repro.launch — meshes, the training CLI, compiled-step introspection."""
