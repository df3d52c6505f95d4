"""h2o3_genmodel — standalone MOJO scoring runtime.

The dependency-free counterpart of the reference's h2o-genmodel jar
(h2o-genmodel/src/main/java/hex/genmodel/easy/EasyPredictModelWrapper.java:1,
MojoModel.java:1): loads a MOJO zip exported by h2o3_tpu and scores rows
using ONLY numpy + the standard library — no h2o3_tpu, no jax, no server.

Usage:
    import h2o3_genmodel as gm
    model = gm.load_mojo("model.zip")
    res = model.predict({"x1": 0.3, "g": "b"})       # one row, EasyPredict
    tbl = model.score(cols)                          # batch: dict of arrays

CLI (hex/genmodel/tools/PredictCsv.java analog):
    python -m h2o3_genmodel.predict_csv --mojo model.zip \
        --input in.csv --output out.csv

AOT artifacts (the serving-tier lineage; needs jax at score time):
    scorer = gm.load_artifact("model_artifact/")   # AOT executable + HLO
    tbl = scorer.score(cols)
    python -m h2o3_genmodel.aot_predict --artifact model_artifact/ \
        --input in.csv --output out.csv

`levels.py` is the one numpy-only module that h2o3_tpu imports from here
(models/tree/compressed.py, artifact/packer.py): the layout of a forest for
the device walk, shared so that an artifact's runner and its exporter
cannot lay the same stored arrays out differently.
"""

from h2o3_genmodel.aot import AotScorer, load_artifact
from h2o3_genmodel.easy import (AnomalyPrediction, BinomialPrediction,
                                ClusteringPrediction, EasyPredictor,
                                MultinomialPrediction, RegressionPrediction,
                                load_mojo)

__version__ = "1.0.0"
__all__ = ["load_mojo", "EasyPredictor", "BinomialPrediction",
           "MultinomialPrediction", "RegressionPrediction",
           "ClusteringPrediction", "AnomalyPrediction",
           "load_artifact", "AotScorer", "__version__"]
