"""The paper's primary contribution: fast SPSD approximation + fast CUR.

Public API re-exports.
"""
from repro.core.kernelop import (DenseSPSD, LinearKernel, PairwiseKernel,
                                 RBFKernel, SPSDOperator, as_operator)
from repro.kernels.pairwise.specs import (KernelSpec, get_spec,
                                          register_kernel, registered_kernels)
from repro.core.sweep import (ColumnGatherPlan, DiagPlan, FrobeniusPlan,
                              GramPlan, MatmulPlan, ProjResidualColNormPlan,
                              ResidualFroPlan, RowQuadFormPlan,
                              SketchRightPlan, mesh_data_size, sweep_operator,
                              sweep_panels)
from repro.core.instrument import CountingOperator
from repro.core.selection import (LeveragePolicy, SelectionPolicy,
                                  UniformAdaptive2Policy, UniformPolicy,
                                  get_policy, register_policy,
                                  registered_policies, residual_column_norms)
# per-spec streaming calibration lives in repro.kernels.pairwise.calibrate
from repro.core.leverage import (column_leverage_scores,
                                 column_leverage_scores_gram,
                                 orthonormal_basis, pinv, row_coherence,
                                 row_leverage_scores, row_leverage_scores_gram)
from repro.core.sketch import (SKETCH_KINDS, ColumnSketch, CountSketch,
                               GaussianSketch, MaskedSketch, SRHTSketch,
                               count_sketch, fwht, leverage_column_sketch,
                               make_sketch, plan_for_sketch, right_streaming,
                               srht_sketch, subset_union_sketch, sym_streaming,
                               uniform_column_sketch)
from repro.core.spsd import (SPSDApprox, bucket_by_size, error_vs_best_rank_k,
                             fast_U, fast_model, fast_model_batched,
                             fast_model_from_C, fast_model_ragged,
                             fast_model_with_error, nystrom_U, nystrom_model,
                             prototype_U, prototype_model, relative_error,
                             sample_C, streaming_topk_eigvals)
from repro.core.cur import (CURApprox, adaptive_row_indices,
                            blocked_right_sketch, drineas08_U, fast_U_cur,
                            fast_cur, optimal_U, optimal_cur)
from repro.core.eig import (EigResult, approx_eigh, kpca_features,
                            kpca_transform, misalignment, spectral_embedding,
                            woodbury_solve)
from repro.core.adaptive import uniform_adaptive2_indices
from repro.core.sketched_attention import (LandmarkState, build_landmark_state,
                                           landmark_decode, select_landmarks,
                                           signed_den_floor,
                                           sketched_attention)

__all__ = [k for k in dir() if not k.startswith("_")]
