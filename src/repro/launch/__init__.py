"""`repro.launch` — mesh, input specs, dry-run, roofline, train/serve CLIs.

Importing this package never touches jax device state (meshes are built
by functions, the dry-run sets XLA_FLAGS in its entry point).
"""
