from repro.kernels.unpack_bits.kernel import (stage_tiles,  # noqa: F401
                                              unit_words_pallas)
from repro.kernels.unpack_bits.ops import (BACKENDS,  # noqa: F401
                                           make_unpacker, scratch_nbytes,
                                           select_backend, unpack_bits)
from repro.kernels.unpack_bits.ref import unpack_bits_ref  # noqa: F401
