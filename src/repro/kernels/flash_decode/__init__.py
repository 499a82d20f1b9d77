from repro.kernels.flash_decode.ops import (  # noqa: F401
    flash_decode,
    mla_flash_decode,
    paged_flash_decode,
    paged_flash_extend,
    paged_mla_flash_decode,
    paged_mla_flash_extend,
)
from repro.kernels.flash_decode.kernel import (  # noqa: F401
    flash_decode_pallas,
    mla_flash_decode_pallas,
    paged_flash_decode_pallas,
    paged_flash_extend_pallas,
    paged_mla_flash_decode_pallas,
    paged_mla_flash_extend_pallas,
    paged_work_len,
    paged_work_list,
)
from repro.kernels.flash_decode.ref import (  # noqa: F401
    flash_decode_ref,
    mla_flash_decode_ref,
    paged_flash_decode_ref,
    paged_flash_extend_ref,
    paged_mla_flash_decode_ref,
    paged_mla_flash_extend_ref,
)
