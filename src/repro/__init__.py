"""repro — paper reproduction grown toward a production jax system."""
