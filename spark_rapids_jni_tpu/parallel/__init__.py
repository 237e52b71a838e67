from spark_rapids_jni_tpu.parallel.multihost import (
    initialize as initialize_multihost,
    is_multihost,
    make_pod_mesh,
)
from spark_rapids_jni_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    make_mesh,
    data_sharding,
    model_sharding,
    replicated,
)
from spark_rapids_jni_tpu.parallel.shuffle import (
    ShuffleResult,
    all_to_all_shuffle,
)
from spark_rapids_jni_tpu.parallel.table_shuffle import (
    PaddedStrings,
    ShuffledTable,
    materialize_strings,
    pad_strings,
    shuffle_table,
)

__all__ = [
    "DATA_AXIS",
    "MODEL_AXIS",
    "make_mesh",
    "data_sharding",
    "model_sharding",
    "replicated",
    "PaddedStrings",
    "ShuffleResult",
    "ShuffledTable",
    "all_to_all_shuffle",
    "initialize_multihost",
    "is_multihost",
    "make_pod_mesh",
    "materialize_strings",
    "pad_strings",
    "shuffle_table",
]
