"""Core library: the paper's contribution as composable JAX modules."""
from .types import GFactors, TFactors, SCALE, SHEAR
from .gtransform import (approximate_symmetric, g_init, g_polish, g_objective,
                         g_to_dense, gapply, lemma1_spectrum)
from .ttransform import (approximate_general, t_init, t_polish, t_objective,
                         t_to_dense, tapply, t_reconstruct, lemma2_spectrum)
from .staging import (default_cut_ladder, pack_g, pack_g_adjoint,
                      pack_g_batch, pack_g_batch_pair, pack_g_pair, pack_t,
                      pack_t_batch, pack_t_batch_pair, pack_t_inverse,
                      pack_t_pair, select_cut)
from .eigenbasis import ApproxEigenbasis, pad_ragged
from .fgft import (FGFT, build_fgft, laplacian, prefix_relative_error,
                   relative_error)
from .baselines import (truncated_jacobi, factorize_orthonormal,
                        rank_r_symmetric, rank_r_general)
from .fastlinear import (ButterflyParams, ButterflyPattern, fft_pattern,
                         butterfly_init, butterfly_apply, compress_linear,
                         compressed_linear_apply, CompressedLinear)
