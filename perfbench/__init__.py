"""The benchmark of parameter_server_distributed_tpu: one command runs one
cell once (see README.md in this directory)."""
