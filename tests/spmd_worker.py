"""Worker process for the SPMD-dispatch stack test (not a pytest file).

Two of these form a 2-host deployment against a shared store server:
process 0 plays the coordinator (submits a build_model job through the
SPMD dispatcher, exactly as the model_builder REST handler does in
multi-host mode), process 1 plays the worker host (run_worker_loop).
Both enter the same fit over the global 8-device mesh; only the
coordinator writes predictions to the store.
"""

import sys


def main() -> None:
    process_id = int(sys.argv[1])
    num_processes = int(sys.argv[2])
    coordinator = sys.argv[3]
    store_url = sys.argv[4]
    images_dir = sys.argv[5]

    import os

    os.environ["LO_COORDINATOR"] = coordinator
    os.environ["LO_NUM_PROCESSES"] = str(num_processes)
    os.environ["LO_PROCESS_ID"] = str(process_id)

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_enable_x64", True)

    from learningorchestra_tpu.parallel.multihost import initialize_from_env

    assert initialize_from_env(), "multi-host runtime did not come up"

    from learningorchestra_tpu.core.store_service import connect
    from learningorchestra_tpu.services.runner import make_dispatcher

    store = connect(store_url)
    dispatcher = make_dispatcher(store, images_dir)

    if process_id == 0:
        dispatcher.submit(
            "build_model",
            {
                "training_filename": "spmd_train",
                "test_filename": "spmd_train",
                "preprocessor_code": PREPROCESSOR,
                "classificators_list": ["lr", "dt", "gb"],
            },
        )
        dispatcher.shutdown_workers()
        print("coordinator: job done", flush=True)
    else:
        dispatcher.run_worker_loop()
        print("worker: loop exited", flush=True)


PREPROCESSOR = """
from pyspark.ml.feature import VectorAssembler
assembler = VectorAssembler(inputCols=["f1", "f2"], outputCol="features")
features_training = assembler.transform(training_df)
features_testing = assembler.transform(testing_df)
features_evaluation = features_training
"""


if __name__ == "__main__":
    main()
