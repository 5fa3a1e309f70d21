# Pallas kernels, each with its pure-jnp oracle in ref.py. A model layer
# selects a kernel itself, from platform, shapes, config or mesh (see
# models/attention.decode_kv_block); no caller-set option routes into one.
