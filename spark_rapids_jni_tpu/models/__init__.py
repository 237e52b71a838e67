from spark_rapids_jni_tpu.models.nds import (
    QueryStepConfig,
    QueryStepOut,
    local_query_step,
    make_distributed_query_step,
    make_example_batch,
)
from spark_rapids_jni_tpu.models.q3 import (
    Q3Row,
    make_distributed_q3,
    q3_local,
    run_distributed_q3,
    run_distributed_q3_columns,
)
from spark_rapids_jni_tpu.models.q5 import (
    Q5Row,
    make_distributed_q5,
    q5_local,
    run_distributed_q5,
)
from spark_rapids_jni_tpu.models.q97 import (
    Q97Batch,
    Q97Out,
    combine_q97_outs,
    make_distributed_q97_columns,
    run_distributed_q97,
    run_q97_piece,
    split_q97_batch,
)
from spark_rapids_jni_tpu.models.tpcds import (
    Q3Data,
    Q5Data,
    generate_q3_data,
    generate_q5_data,
)

__all__ = [
    "QueryStepConfig",
    "QueryStepOut",
    "Q3Data",
    "Q3Row",
    "Q5Data",
    "Q5Row",
    "Q97Batch",
    "Q97Out",
    "generate_q3_data",
    "generate_q5_data",
    "make_distributed_q3",
    "q3_local",
    "run_distributed_q3",
    "run_distributed_q3_columns",
    "make_distributed_q5",
    "make_distributed_q97_columns",
    "q5_local",
    "run_distributed_q5",
    "local_query_step",
    "make_distributed_query_step",
    "make_example_batch",
    "combine_q97_outs",
    "run_distributed_q97",
    "run_q97_piece",
    "split_q97_batch",
]
