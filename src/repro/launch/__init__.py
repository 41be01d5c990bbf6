"""Distribution/launch layer: device meshes, the compile cache, and the LM
islands and serving drivers with their step functions."""
