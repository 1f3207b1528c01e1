"""Bytes the owner exchange moves BETWEEN chips in one epoch: the
server's `mesh_a2a_bytes` (`deneva_tpu/parallel/mesh.a2a_bytes_per_epoch`:
D x (D - 1) blocks of `pair_cap` lanes x 9 B, from the block shapes
`execute_mc` cuts at the batch's real width; static).  A server that
armed no mesh prints no such key: None."""


def read(ctx):
    return ctx["server"]["summary"].get("mesh_a2a_bytes")
