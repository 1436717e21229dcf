from repro.core.sparse.formats import (  # noqa: F401
    ChunkedCSC,
    HostCSC,
    HostCSR,
    PaddedCSC,
    PaddedCSR,
    TieredCSC,
    coo_to_host,
    dense_to_host,
    dense_to_padded,
    tiered_from_padded,
)
