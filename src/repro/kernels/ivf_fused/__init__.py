"""Fused IVF hot path: gather → score → streaming top-k in one kernel.

One ``pallas_call`` covers the entire probed-candidate pipeline for every
scorer backend (float / fp16 / int8 / 1-bit), list-major: the batch's
probe table is inverted on the device into the sorted distinct probed
lists, a scalar-prefetched step table, so each grid step DMAs one chunk of
one inverted list from the list-major storage once per batch, scores it in
VMEM against every query row with the backend's MXU path, and merges it
into the rows' running top-k — the (Q, nprobe·max_len) candidate matrix
never exists in HBM.
"""
