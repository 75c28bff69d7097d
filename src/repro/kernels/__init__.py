from repro.kernels.dispatch import (pallas_aggregate,
                                    pallas_masked_aggregate,
                                    pallas_masked_supported,
                                    pallas_scaled_aggregate,
                                    pallas_scaled_masked_aggregate,
                                    pallas_scaled_supported,
                                    pallas_supported)
from repro.kernels.ops import (kernel_bulyan, kernel_bulyan_masked,
                               kernel_cge, kernel_cge_masked,
                               kernel_coordinate_median, kernel_krum,
                               kernel_krum_masked, kernel_m_krum,
                               kernel_m_krum_masked, kernel_mda,
                               kernel_mda_masked, kernel_multi_krum,
                               kernel_multi_krum_masked,
                               kernel_pairwise_sq_dists,
                               kernel_trimmed_mean)
from repro.kernels.tiling import default_interpret
from repro.kernels.wsum import clipped_weighted_sum

__all__ = ["kernel_coordinate_median", "kernel_trimmed_mean", "kernel_krum",
           "kernel_cge", "kernel_multi_krum", "kernel_m_krum", "kernel_mda",
           "kernel_bulyan", "kernel_krum_masked", "kernel_cge_masked",
           "kernel_multi_krum_masked", "kernel_m_krum_masked",
           "kernel_mda_masked", "kernel_bulyan_masked",
           "kernel_pairwise_sq_dists", "clipped_weighted_sum",
           "pallas_aggregate", "pallas_masked_aggregate",
           "pallas_scaled_aggregate", "pallas_scaled_masked_aggregate",
           "pallas_supported", "pallas_masked_supported",
           "pallas_scaled_supported", "default_interpret"]
