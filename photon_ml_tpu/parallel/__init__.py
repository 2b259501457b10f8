from photon_ml_tpu.parallel.mesh import (  # noqa: F401
    DATA_AXIS, FEATURE_AXIS, data_sharding, feature_sharding,
    initialize_multihost, make_mesh, replicated, shard_leading,
)
from photon_ml_tpu.parallel.fixed_effect import (  # noqa: F401
    fit_fixed_effect, pad_batch_to_mesh, score_fixed_effect, shard_objective,
    stage_objective,
)
from photon_ml_tpu.parallel.mesh_residency import (  # noqa: F401
    MeshResidency, TransferStats, default_residency, transfer_snapshot,
)
from photon_ml_tpu.parallel.random_effect import (  # noqa: F401
    EntityBlocks, fit_random_effects, random_effect_variances,
    score_by_entity, score_entity_blocks,
)
from photon_ml_tpu.parallel.factored import (  # noqa: F401
    FactoredSolveResult, ProjectionRows, fit_factored_random_effects,
    gaussian_projection_matrix, project_blocks, refit_latent_projection,
)
