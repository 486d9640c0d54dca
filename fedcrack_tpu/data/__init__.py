from fedcrack_tpu.data.pipeline import (  # noqa: F401
    ArrayDataset,
    CrackDataset,
    SamplePool,
    as_model_batch,
    dataset_from_source,
    list_pairs,
    load_example,
    normalize_images,
    reference_split,
)
from fedcrack_tpu.data.sharding import partition_iid, partition_skew  # noqa: F401
from fedcrack_tpu.data.synthetic import synth_crack_batch, write_synthetic_dataset  # noqa: F401
from fedcrack_tpu.data.textdiff import block_diffusion_weights, stage_pair  # noqa: F401
