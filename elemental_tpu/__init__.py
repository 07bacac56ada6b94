"""elemental_tpu: TPU-native distributed dense linear algebra.

A from-scratch JAX/XLA/shard_map re-design of the capabilities of the
reference framework (Elemental: distributed-memory dense linear algebra over
a 2-D process grid).  See SURVEY.md for the blueprint.
"""
from .core.dist import Dist, MC, MD, MR, VC, VR, STAR, CIRC, LEGAL_PAIRS
from .core.grid import Grid, default_grid, set_default_grid
from .core.environment import (blocksize, set_blocksize, push_blocksize,
                               pop_blocksize, blocksize_scope, Timer, Args,
                               ProgressLog)
from .core.ctrl import (SignCtrl, PolarCtrl, HermitianEigCtrl, SVDCtrl,
                        SchurCtrl, PseudospecCtrl, LDLPivotCtrl, QRCtrl,
                        LeastSquaresCtrl)
from .core.distmatrix import (DistMatrix, from_global, to_global,
                              zeros, remote_updates)
from .core.block import (BlockMatrix, block_from_global, block_from_array,
                         block_to_global, block_to_cyclic, block_from_cyclic,
                         as_elemental)
from .core.multivec import (DistMultiVec, mv_from_global, mv_to_global,
                            mv_zeros, mv_axpy, mv_scale, mv_dot, mv_nrm2,
                            mv_remote_updates, mv_to_distmatrix,
                            mv_from_distmatrix)
from .redist.engine import redistribute, transpose_dist, panel_spread

__version__ = "0.2.0"

from . import (blas, lapack, matrices, optimization, control, lattice, tune,
               obs, resilience, serve)
from .resilience import (certified_solve, HealthMonitor, last_health_report,
                         FaultPlan, FaultSpec, fault_injection)
from .serve import SolverService, Deadline
from .blas import (gemm, herk, syrk, trrk, trsm, trr2k, her2k, syr2k,
                   hemm, symm, trmm, two_sided_trsm, two_sided_trmm,
                   multishift_trsm, quasi_trsm)
from .blas import gemv, ger, hemv, symv, her2, trmv, trsv
from .blas import (axpy, scale, fill, entrywise_map, hadamard,
                   index_dependent_fill, make_trapezoidal, shift_diagonal,
                   make_symmetric, get_diagonal, set_diagonal,
                   diagonal_scale, diagonal_solve, frobenius_norm, max_norm,
                   one_norm, infinity_norm, dot, dotu, trace, transpose,
                   adjoint, real_part, imag_part, max_abs_loc, max_loc,
                   scale_trapezoid, axpy_trapezoid, safe_scale,
                   get_submatrix, set_submatrix)
from .lapack import (cholesky, hpd_solve, cholesky_solve_after,
                     cholesky_pivoted, cholesky_mod)
from .lapack import (lu, lu_solve, lu_solve_after, permute_rows,
                     permute_cols, lu_full_pivot)
from .lapack import mixed_solve
from .lapack import (qr, apply_q, explicit_q, least_squares, tsqr, lq,
                     apply_q_lq, explicit_l, qr_col_piv, rq)
from .lapack import ridge, tikhonov, lse, glm
from .lapack import (hermitian_tridiag, apply_q_herm_tridiag, hessenberg,
                     apply_q_hessenberg, bidiag, apply_p_bidiag)
from .lapack import ldl, ldl_solve_after, symmetric_solve, hermitian_solve, inertia
from .lapack import (polar, sign, inverse, triangular_inverse, hpd_inverse,
                     pseudoinverse, square_root, hpd_square_root)
from .lapack import (herm_eig, skew_herm_eig, herm_gen_def_eig, hermitian_svd,
                     svd, tridiag_eig)
from .redist.interior import interior_view, interior_update, vstack, hstack
from .optimization import (MehrotraCtrl, lp, qp, socp, soft_threshold, svt,
                           bp, lav, nnls, lasso, svm, rpca,
                           lp_affine, qp_affine, socp_affine,
                           ruiz_equil, geom_equil, symmetric_ruiz_equil,
                           lp_sparse, lav_sparse, bp_sparse,
                           cp, ds, en, nmf, sparse_inv_cov,
                           long_only_portfolio, tv)
from .control import sylvester, lyapunov, riccati
from .lattice import lll, is_lll_reduced, shortest_vector
from .lapack.schur import schur, triang_eig, eig, pseudospectra
from .lapack.props import (determinant, safe_determinant, hpd_determinant,
                           two_norm_estimate, condition, nuclear_norm,
                           schatten_norm, two_norm)
from .io import (print_matrix, write_matrix, read_matrix, checkpoint,
                 restore, write_matrix_market, read_matrix_market, display,
                 spy)
from . import sparse
from .sparse import (Graph, DistGraph, SparseMatrix, DistSparseMatrix,
                     DistMap, sparse_from_coo, dist_sparse_from_coo,
                     cg, cgls, gmres, sparse_direct_solve)
