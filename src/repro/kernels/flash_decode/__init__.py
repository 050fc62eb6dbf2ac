from repro.kernels.flash_decode.ops import (flash_decode,  # noqa: F401
                                            paged_flash_verify,
                                            paged_flash_verify_mla)
from repro.kernels.flash_decode.ref import (decode_reference,  # noqa: F401
                                            gather_kv,
                                            paged_decode_reference,
                                            paged_mla_decode_reference)
