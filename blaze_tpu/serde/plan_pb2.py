# -*- coding: utf-8 -*-
# Generated by the protocol buffer compiler.  DO NOT EDIT!
# source: blaze_tpu/serde/plan.proto
"""Generated protocol buffer code."""
from google.protobuf.internal import builder as _builder
from google.protobuf import descriptor as _descriptor
from google.protobuf import descriptor_pool as _descriptor_pool
from google.protobuf import symbol_database as _symbol_database
# @@protoc_insertion_point(imports)

_sym_db = _symbol_database.Default()




DESCRIPTOR = _descriptor_pool.Default().AddSerializedFile(b'\n\x1a\x62laze_tpu/serde/plan.proto\x12\tblaze_tpu\"\xa3\x02\n\rDataTypeProto\x12!\n\x04kind\x18\x01 \x01(\x0e\x32\x13.blaze_tpu.TypeKind\x12\x11\n\tprecision\x18\x02 \x01(\r\x12\r\n\x05scale\x18\x03 \x01(\r\x12\x14\n\x0cstring_width\x18\x04 \x01(\r\x12&\n\x04\x65lem\x18\x05 \x01(\x0b\x32\x18.blaze_tpu.DataTypeProto\x12%\n\x03key\x18\x06 \x01(\x0b\x32\x18.blaze_tpu.DataTypeProto\x12\'\n\x05value\x18\x07 \x01(\x0b\x32\x18.blaze_tpu.DataTypeProto\x12,\n\rstruct_fields\x18\x08 \x03(\x0b\x32\x15.blaze_tpu.FieldProto\x12\x11\n\tmax_elems\x18\t \x01(\r\"U\n\nFieldProto\x12\x0c\n\x04name\x18\x01 \x01(\t\x12\'\n\x05\x64type\x18\x02 \x01(\x0b\x32\x18.blaze_tpu.DataTypeProto\x12\x10\n\x08nullable\x18\x03 \x01(\x08\"4\n\x0bSchemaProto\x12%\n\x06\x66ields\x18\x01 \x03(\x0b\x32\x15.blaze_tpu.FieldProto\"\xaa\x01\n\x0cLiteralValue\x12\'\n\x05\x64type\x18\x01 \x01(\x0b\x32\x18.blaze_tpu.DataTypeProto\x12\x0f\n\x07is_null\x18\x02 \x01(\x08\x12\x14\n\nbool_value\x18\x03 \x01(\x08H\x00\x12\x13\n\tint_value\x18\x04 \x01(\x03H\x00\x12\x15\n\x0b\x66loat_value\x18\x05 \x01(\x01H\x00\x12\x15\n\x0b\x62ytes_value\x18\x06 \x01(\x0cH\x00\x42\x07\n\x05value\"\xad\x06\n\x08\x45xprNode\x12\x10\n\x06\x63olumn\x18\x01 \x01(\tH\x00\x12*\n\x07literal\x18\x02 \x01(\x0b\x32\x17.blaze_tpu.LiteralValueH\x00\x12\'\n\x06\x62inary\x18\x03 \x01(\x0b\x32\x15.blaze_tpu.BinaryExprH\x00\x12\"\n\x03not\x18\x04 \x01(\x0b\x32\x13.blaze_tpu.ExprNodeH\x00\x12&\n\x07is_null\x18\x05 \x01(\x0b\x32\x13.blaze_tpu.ExprNodeH\x00\x12*\n\x0bis_not_null\x18\x06 \x01(\x0b\x32\x13.blaze_tpu.ExprNodeH\x00\x12#\n\x04\x63\x61st\x18\x07 \x01(\x0b\x32\x13.blaze_tpu.CastExprH\x00\x12#\n\x04\x63\x61se\x18\x08 \x01(\x0b\x32\x13.blaze_tpu.CaseExprH\x00\x12(\n\x07in_list\x18\t \x01(\x0b\x32\x15.blaze_tpu.InListExprH\x00\x12#\n\x04like\x18\n \x01(\x0b\x32\x13.blaze_tpu.LikeExprH\x00\x12\x30\n\x0bscalar_func\x18\x0b \x01(\x0b\x32\x19.blaze_tpu.ScalarFuncExprH\x00\x12%\n\x05\x61lias\x18\x0c \x01(\x0b\x32\x14.blaze_tpu.AliasExprH\x00\x12*\n\x08host_udf\x18\r \x01(\x0b\x32\x16.blaze_tpu.HostUdfExprH\x00\x12;\n\x11get_indexed_field\x18\x0e \x01(\x0b\x32\x1e.blaze_tpu.GetIndexedFieldExprH\x00\x12\x33\n\rget_map_value\x18\x0f \x01(\x0b\x32\x1a.blaze_tpu.GetMapValueExprH\x00\x12\x39\n\x10get_struct_field\x18\x10 \x01(\x0b\x32\x1d.blaze_tpu.GetStructFieldExprH\x00\x12\x32\n\x0cnamed_struct\x18\x11 \x01(\x0b\x32\x1a.blaze_tpu.NamedStructExprH\x00\x12;\n\x11spark_udf_wrapper\x18\x12 \x01(\x0b\x32\x1e.blaze_tpu.SparkUdfWrapperExprH\x00\x42\x06\n\x04\x65xpr\"H\n\x13GetIndexedFieldExpr\x12\"\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\r\n\x05index\x18\x02 \x01(\x03\"[\n\x0fGetMapValueExpr\x12\"\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12$\n\x03key\x18\x02 \x01(\x0b\x32\x17.blaze_tpu.LiteralValue\"F\n\x12GetStructFieldExpr\x12\"\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x0c\n\x04name\x18\x02 \x01(\t\"D\n\x0fNamedStructExpr\x12\r\n\x05names\x18\x01 \x03(\t\x12\"\n\x05\x65xprs\x18\x02 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\"_\n\nBinaryExpr\x12\n\n\x02op\x18\x01 \x01(\t\x12!\n\x04left\x18\x02 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\"\n\x05right\x18\x03 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\"T\n\x08\x43\x61stExpr\x12\"\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12$\n\x02to\x18\x02 \x01(\x0b\x32\x18.blaze_tpu.DataTypeProto\"X\n\nCaseBranch\x12&\n\tcondition\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\"\n\x05value\x18\x02 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\"m\n\x08\x43\x61seExpr\x12\'\n\x08\x62ranches\x18\x01 \x03(\x0b\x32\x15.blaze_tpu.CaseBranch\x12&\n\telse_expr\x18\x02 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x10\n\x08has_else\x18\x03 \x01(\x08\"f\n\nInListExpr\x12\"\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12#\n\x06values\x18\x02 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x0f\n\x07negated\x18\x03 \x01(\x08\"P\n\x08LikeExpr\x12\"\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x0f\n\x07pattern\x18\x02 \x01(\t\x12\x0f\n\x07negated\x18\x03 \x01(\x08\"A\n\x0eScalarFuncExpr\x12\x0c\n\x04name\x18\x01 \x01(\t\x12!\n\x04\x61rgs\x18\x02 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\"=\n\tAliasExpr\x12\"\n\x05\x63hild\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x0c\n\x04name\x18\x02 \x01(\t\"G\n\x0bHostUdfExpr\x12\x0f\n\x07payload\x18\x01 \x01(\x0c\x12\'\n\x05\x64type\x18\x02 \x01(\x0b\x32\x18.blaze_tpu.DataTypeProto\"\x8a\x01\n\x13SparkUdfWrapperExpr\x12\x12\n\nserialized\x18\x01 \x01(\x0c\x12\'\n\x05\x64type\x18\x02 \x01(\x0b\x32\x18.blaze_tpu.DataTypeProto\x12!\n\x04\x61rgs\x18\x03 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x13\n\x0b\x65xpr_string\x18\x04 \x01(\t\"\xd4\n\n\x10PhysicalPlanNode\x12\x30\n\x0bmemory_scan\x18\x01 \x01(\x0b\x32\x19.blaze_tpu.MemoryScanNodeH\x00\x12\x32\n\x0cparquet_scan\x18\x02 \x01(\x0b\x32\x1a.blaze_tpu.ParquetScanNodeH\x00\x12)\n\x07project\x18\x03 \x01(\x0b\x32\x16.blaze_tpu.ProjectNodeH\x00\x12\'\n\x06\x66ilter\x18\x04 \x01(\x0b\x32\x15.blaze_tpu.FilterNodeH\x00\x12!\n\x03\x61gg\x18\x05 \x01(\x0b\x32\x12.blaze_tpu.AggNodeH\x00\x12#\n\x04sort\x18\x06 \x01(\x0b\x32\x13.blaze_tpu.SortNodeH\x00\x12%\n\x05limit\x18\x07 \x01(\x0b\x32\x14.blaze_tpu.LimitNodeH\x00\x12%\n\x05union\x18\x08 \x01(\x0b\x32\x14.blaze_tpu.UnionNodeH\x00\x12\x36\n\x0erename_columns\x18\t \x01(\x0b\x32\x1c.blaze_tpu.RenameColumnsNodeH\x00\x12:\n\x10\x65mpty_partitions\x18\n \x01(\x0b\x32\x1e.blaze_tpu.EmptyPartitionsNodeH\x00\x12%\n\x05\x64\x65\x62ug\x18\x0b \x01(\x0b\x32\x14.blaze_tpu.DebugNodeH\x00\x12:\n\x10\x63oalesce_batches\x18\x0c \x01(\x0b\x32\x1e.blaze_tpu.CoalesceBatchesNodeH\x00\x12\x36\n\x0eshuffle_writer\x18\r \x01(\x0b\x32\x1c.blaze_tpu.ShuffleWriterNodeH\x00\x12.\n\nipc_reader\x18\x0e \x01(\x0b\x32\x18.blaze_tpu.IpcReaderNodeH\x00\x12.\n\nipc_writer\x18\x0f \x01(\x0b\x32\x18.blaze_tpu.IpcWriterNodeH\x00\x12\x36\n\x0e\x62roadcast_join\x18\x10 \x01(\x0b\x32\x1c.blaze_tpu.BroadcastJoinNodeH\x00\x12,\n\thash_join\x18\x11 \x01(\x0b\x32\x17.blaze_tpu.HashJoinNodeH\x00\x12\x37\n\x0fsort_merge_join\x18\x12 \x01(\x0b\x32\x1c.blaze_tpu.SortMergeJoinNodeH\x00\x12\'\n\x06window\x18\x13 \x01(\x0b\x32\x15.blaze_tpu.WindowNodeH\x00\x12\'\n\x06\x65xpand\x18\x14 \x01(\x0b\x32\x15.blaze_tpu.ExpandNodeH\x00\x12+\n\x08generate\x18\x15 \x01(\x0b\x32\x17.blaze_tpu.GenerateNodeH\x00\x12\x32\n\x0cparquet_sink\x18\x16 \x01(\x0b\x32\x1a.blaze_tpu.ParquetSinkNodeH\x00\x12=\n\x12rss_shuffle_writer\x18\x17 \x01(\x0b\x32\x1f.blaze_tpu.RssShuffleWriterNodeH\x00\x12*\n\x08orc_scan\x18\x18 \x01(\x0b\x32\x16.blaze_tpu.OrcScanNodeH\x00\x12Q\n\x1d\x62roadcast_join_build_hash_map\x18\x19 \x01(\x0b\x32(.blaze_tpu.BroadcastJoinBuildHashMapNodeH\x00\x12\x39\n\x10\x62loom_filter_agg\x18\x1a \x01(\x0b\x32\x1d.blaze_tpu.BloomFilterAggNodeH\x00\x12.\n\nobject_agg\x18\x1b \x01(\x0b\x32\x18.blaze_tpu.ObjectAggNodeH\x00\x42\x06\n\x04node\"e\n\x0eMemoryScanNode\x12\x13\n\x0bresource_id\x18\x01 \x01(\t\x12&\n\x06schema\x18\x02 \x01(\x0b\x32\x16.blaze_tpu.SchemaProto\x12\x16\n\x0enum_partitions\x18\x03 \x01(\r\"\xa2\x02\n\x0fParquetScanNode\x12&\n\x06schema\x18\x01 \x01(\x0b\x32\x16.blaze_tpu.SchemaProto\x12\x13\n\x0b\x66ile_groups\x18\x02 \x03(\t\x12&\n\tpredicate\x18\x03 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x12\n\nbatch_rows\x18\x04 \x01(\r\x12*\n\x0b\x66ile_ranges\x18\x05 \x03(\x0b\x32\x15.blaze_tpu.FileRanges\x12\x30\n\x10partition_schema\x18\x06 \x01(\x0b\x32\x16.blaze_tpu.SchemaProto\x12\x38\n\x10partition_values\x18\x07 \x03(\x0b\x32\x1e.blaze_tpu.FilePartitionValues\"+\n\nFileRanges\x12\r\n\x05start\x18\x01 \x03(\x04\x12\x0e\n\x06length\x18\x02 \x03(\x12\"@\n\x13\x46ilePartitionValues\x12)\n\x05\x66iles\x18\x01 \x03(\x0b\x32\x1a.blaze_tpu.PartitionValues\":\n\x0fPartitionValues\x12\'\n\x06values\x18\x01 \x03(\x0b\x32\x17.blaze_tpu.LiteralValue\"\x86\x01\n\x0bOrcScanNode\x12&\n\x06schema\x18\x01 \x01(\x0b\x32\x16.blaze_tpu.SchemaProto\x12\x13\n\x0b\x66ile_groups\x18\x02 \x03(\t\x12&\n\tpredicate\x18\x03 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x12\n\nbatch_rows\x18\x04 \x01(\r\"l\n\x0bProjectNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\"\n\x05\x65xprs\x18\x02 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\r\n\x05names\x18\x03 \x03(\t\"\xa3\x01\n\nFilterNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12&\n\tpredicate\x18\x02 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12*\n\rproject_exprs\x18\x03 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x15\n\rproject_names\x18\x04 \x03(\t\"<\n\tNamedExpr\x12!\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x0c\n\x04name\x18\x02 \x01(\t\"a\n\x10\x41ggFunctionProto\x12\n\n\x02\x66n\x18\x01 \x01(\t\x12!\n\x04\x65xpr\x18\x02 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x10\n\x08has_expr\x18\x03 \x01(\x08\x12\x0c\n\x04name\x18\x04 \x01(\t\"\xcf\x01\n\x12\x42loomFilterAggNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12!\n\x04\x65xpr\x18\x02 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x10\n\x08has_expr\x18\x03 \x01(\x08\x12\x0c\n\x04name\x18\x04 \x01(\t\x12 \n\x04mode\x18\x05 \x01(\x0e\x32\x12.blaze_tpu.AggMode\x12\x16\n\x0e\x65xpected_items\x18\x06 \x01(\x03\x12\x10\n\x08num_bits\x18\x07 \x01(\x03\"\xce\x01\n\x07\x41ggNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12 \n\x04mode\x18\x02 \x01(\x0e\x32\x12.blaze_tpu.AggMode\x12\'\n\tgroupings\x18\x03 \x03(\x0b\x32\x14.blaze_tpu.NamedExpr\x12)\n\x04\x61ggs\x18\x04 \x03(\x0b\x32\x1b.blaze_tpu.AggFunctionProto\x12!\n\x19supports_partial_skipping\x18\x05 \x01(\x08\"[\n\x0eSortFieldProto\x12!\n\x04\x65xpr\x18\x01 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x11\n\tascending\x18\x02 \x01(\x08\x12\x13\n\x0bnulls_first\x18\x03 \x01(\x08\"\x83\x01\n\x08SortNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12)\n\x06\x66ields\x18\x02 \x03(\x0b\x32\x19.blaze_tpu.SortFieldProto\x12\r\n\x05\x66\x65tch\x18\x03 \x01(\x03\x12\x11\n\thas_fetch\x18\x04 \x01(\x08\"F\n\tLimitNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\r\n\x05limit\x18\x02 \x01(\x04\"8\n\tUnionNode\x12+\n\x06inputs\x18\x01 \x03(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\"N\n\x11RenameColumnsNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\r\n\x05names\x18\x02 \x03(\t\"U\n\x13\x45mptyPartitionsNode\x12&\n\x06schema\x18\x01 \x01(\x0b\x32\x16.blaze_tpu.SchemaProto\x12\x16\n\x0enum_partitions\x18\x02 \x01(\r\"U\n\tDebugNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\x0b\n\x03tag\x18\x02 \x01(\t\x12\x0f\n\x07verbose\x18\x03 \x01(\x08\"V\n\x13\x43oalesceBatchesNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\x13\n\x0btarget_rows\x18\x02 \x01(\r\"\x9e\x02\n\x11PartitioningProto\x12/\n\x04kind\x18\x01 \x01(\x0e\x32!.blaze_tpu.PartitioningProto.Kind\x12\"\n\x05\x65xprs\x18\x02 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x16\n\x0enum_partitions\x18\x03 \x01(\r\x12.\n\x0bsort_fields\x18\x04 \x03(\x0b\x32\x19.blaze_tpu.SortFieldProto\x12\x16\n\x0e\x62oundary_words\x18\x05 \x03(\x04\x12\x1a\n\x12num_boundary_words\x18\x06 \x01(\r\"8\n\x04Kind\x12\n\n\x06SINGLE\x10\x00\x12\x08\n\x04HASH\x10\x01\x12\x0f\n\x0bROUND_ROBIN\x10\x02\x12\t\n\x05RANGE\x10\x03\"\xa8\x01\n\x11ShuffleWriterNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\x32\n\x0cpartitioning\x18\x02 \x01(\x0b\x32\x1c.blaze_tpu.PartitioningProto\x12\x18\n\x10output_data_file\x18\x03 \x01(\t\x12\x19\n\x11output_index_file\x18\x04 \x01(\t\"\xa0\x01\n\x14RssShuffleWriterNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\x32\n\x0cpartitioning\x18\x02 \x01(\x0b\x32\x1c.blaze_tpu.PartitioningProto\x12(\n rss_partition_writer_resource_id\x18\x03 \x01(\t\"q\n\rIpcReaderNode\x12&\n\x06schema\x18\x01 \x01(\x0b\x32\x16.blaze_tpu.SchemaProto\x12 \n\x18ipc_provider_resource_id\x18\x02 \x01(\t\x12\x16\n\x0enum_partitions\x18\x03 \x01(\r\"]\n\rIpcWriterNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12 \n\x18ipc_consumer_resource_id\x18\x02 \x01(\t\"\xcd\x02\n\x11\x42roadcastJoinNode\x12*\n\x05\x62uild\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12*\n\x05probe\x18\x02 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\'\n\nbuild_keys\x18\x03 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\'\n\nprobe_keys\x18\x04 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12+\n\tjoin_type\x18\x05 \x01(\x0e\x32\x18.blaze_tpu.JoinTypeProto\x12\x15\n\rbuild_is_left\x18\x06 \x01(\x08\x12\x31\n\x11\x62uild_data_schema\x18\x07 \x01(\x0b\x32\x16.blaze_tpu.SchemaProto\x12\x17\n\x0f\x63\x61\x63hed_build_id\x18\x08 \x01(\t\"n\n\x1d\x42roadcastJoinBuildHashMapNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12!\n\x04keys\x18\x02 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\"\xfc\x01\n\x0cHashJoinNode\x12*\n\x05\x62uild\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12*\n\x05probe\x18\x02 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\'\n\nbuild_keys\x18\x03 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\'\n\nprobe_keys\x18\x04 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12+\n\tjoin_type\x18\x05 \x01(\x0e\x32\x18.blaze_tpu.JoinTypeProto\x12\x15\n\rbuild_is_left\x18\x06 \x01(\x08\"\xfc\x01\n\x11SortMergeJoinNode\x12)\n\x04left\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12*\n\x05right\x18\x02 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12&\n\tleft_keys\x18\x03 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12\'\n\nright_keys\x18\x04 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12+\n\tjoin_type\x18\x05 \x01(\x0e\x32\x18.blaze_tpu.JoinTypeProto\x12\x12\n\nnulls_last\x18\x06 \x01(\x08\"\xba\x02\n\x13WindowFunctionProto\x12\x0c\n\x04kind\x18\x01 \x01(\t\x12\x0c\n\x04name\x18\x02 \x01(\t\x12!\n\x04\x65xpr\x18\x03 \x01(\x0b\x32\x13.blaze_tpu.ExprNode\x12\x10\n\x08has_expr\x18\x04 \x01(\x08\x12\x17\n\x0fwhole_partition\x18\x05 \x01(\x08\x12\x16\n\x0ehas_rows_frame\x18\x06 \x01(\x08\x12\x17\n\x0f\x66rame_preceding\x18\x07 \x01(\x03\x12\x17\n\x0f\x66rame_following\x18\x08 \x01(\x03\x12\x0e\n\x06offset\x18\t \x01(\x03\x12\x14\n\x0cignore_nulls\x18\n \x01(\x08\x12\x17\n\x0fhas_range_frame\x18\x0b \x01(\x08\x12\x17\n\x0frange_preceding\x18\x0c \x01(\x03\x12\x17\n\x0frange_following\x18\r \x01(\x03\"\xc3\x01\n\nWindowNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\x31\n\tfunctions\x18\x02 \x03(\x0b\x32\x1e.blaze_tpu.WindowFunctionProto\x12)\n\x0cpartition_by\x18\x03 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12+\n\x08order_by\x18\x04 \x03(\x0b\x32\x19.blaze_tpu.SortFieldProto\"6\n\x10\x45xpandProjection\x12\"\n\x05\x65xprs\x18\x01 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\"y\n\nExpandNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\x30\n\x0bprojections\x18\x02 \x03(\x0b\x32\x1b.blaze_tpu.ExpandProjection\x12\r\n\x05names\x18\x03 \x03(\t\"\x9f\x02\n\x0cGenerateNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\x11\n\tgenerator\x18\x02 \x01(\t\x12\x19\n\x11generator_payload\x18\x03 \x01(\x0c\x12(\n\x0binput_exprs\x18\x04 \x03(\x0b\x32\x13.blaze_tpu.ExprNode\x12)\n\ngen_fields\x18\x05 \x03(\x0b\x32\x15.blaze_tpu.FieldProto\x12\r\n\x05outer\x18\x06 \x01(\x08\x12\x12\n\nkeep_input\x18\x07 \x01(\x08\x12\x13\n\x0bnative_kind\x18\x08 \x01(\t\x12(\n\x0bnative_expr\x18\t \x01(\x0b\x32\x13.blaze_tpu.ExprNode\"m\n\x0fParquetSinkNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12\x13\n\x0boutput_path\x18\x02 \x01(\t\x12\x19\n\x11partition_columns\x18\x03 \x03(\t\"\x9d\x01\n\rObjectAggNode\x12*\n\x05input\x18\x01 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode\x12 \n\x04mode\x18\x02 \x01(\x0e\x32\x12.blaze_tpu.AggMode\x12\'\n\tgroupings\x18\x03 \x03(\x0b\x32\x14.blaze_tpu.NamedExpr\x12\x15\n\rudafs_payload\x18\x04 \x01(\x0c\"q\n\x0eTaskDefinition\x12\x0f\n\x07task_id\x18\x01 \x01(\t\x12\x10\n\x08stage_id\x18\x02 \x01(\r\x12\x11\n\tpartition\x18\x03 \x01(\r\x12)\n\x04plan\x18\x04 \x01(\x0b\x32\x1b.blaze_tpu.PhysicalPlanNode*\xcf\x01\n\x08TypeKind\x12\x08\n\x04NULL\x10\x00\x12\x08\n\x04\x42OOL\x10\x01\x12\x08\n\x04INT8\x10\x02\x12\t\n\x05INT16\x10\x03\x12\t\n\x05INT32\x10\x04\x12\t\n\x05INT64\x10\x05\x12\x0b\n\x07\x46LOAT32\x10\x06\x12\x0b\n\x07\x46LOAT64\x10\x07\x12\x0b\n\x07\x44\x45\x43IMAL\x10\x08\x12\n\n\x06STRING\x10\t\x12\n\n\x06\x42INARY\x10\n\x12\n\n\x06\x44\x41TE32\x10\x0b\x12\r\n\tTIMESTAMP\x10\x0c\x12\t\n\x05\x41RRAY\x10\r\x12\x07\n\x03MAP\x10\x0e\x12\n\n\x06STRUCT\x10\x0f\x12\n\n\x06OPAQUE\x10\x10*4\n\x07\x41ggMode\x12\x0b\n\x07PARTIAL\x10\x00\x12\x11\n\rPARTIAL_MERGE\x10\x01\x12\t\n\x05\x46INAL\x10\x02*\x86\x01\n\rJoinTypeProto\x12\t\n\x05INNER\x10\x00\x12\x08\n\x04LEFT\x10\x01\x12\t\n\x05RIGHT\x10\x02\x12\x08\n\x04\x46ULL\x10\x03\x12\r\n\tLEFT_SEMI\x10\x04\x12\r\n\tLEFT_ANTI\x10\x05\x12\x0e\n\nRIGHT_SEMI\x10\x06\x12\x0e\n\nRIGHT_ANTI\x10\x07\x12\r\n\tEXISTENCE\x10\x08\x62\x06proto3')

_builder.BuildMessageAndEnumDescriptors(DESCRIPTOR, globals())
_builder.BuildTopDescriptorsAndMessages(DESCRIPTOR, 'blaze_tpu.serde.plan_pb2', globals())
if _descriptor._USE_C_DESCRIPTORS == False:

  DESCRIPTOR._options = None
  _TYPEKIND._serialized_start=9476
  _TYPEKIND._serialized_end=9683
  _AGGMODE._serialized_start=9685
  _AGGMODE._serialized_end=9737
  _JOINTYPEPROTO._serialized_start=9740
  _JOINTYPEPROTO._serialized_end=9874
  _DATATYPEPROTO._serialized_start=42
  _DATATYPEPROTO._serialized_end=333
  _FIELDPROTO._serialized_start=335
  _FIELDPROTO._serialized_end=420
  _SCHEMAPROTO._serialized_start=422
  _SCHEMAPROTO._serialized_end=474
  _LITERALVALUE._serialized_start=477
  _LITERALVALUE._serialized_end=647
  _EXPRNODE._serialized_start=650
  _EXPRNODE._serialized_end=1463
  _GETINDEXEDFIELDEXPR._serialized_start=1465
  _GETINDEXEDFIELDEXPR._serialized_end=1537
  _GETMAPVALUEEXPR._serialized_start=1539
  _GETMAPVALUEEXPR._serialized_end=1630
  _GETSTRUCTFIELDEXPR._serialized_start=1632
  _GETSTRUCTFIELDEXPR._serialized_end=1702
  _NAMEDSTRUCTEXPR._serialized_start=1704
  _NAMEDSTRUCTEXPR._serialized_end=1772
  _BINARYEXPR._serialized_start=1774
  _BINARYEXPR._serialized_end=1869
  _CASTEXPR._serialized_start=1871
  _CASTEXPR._serialized_end=1955
  _CASEBRANCH._serialized_start=1957
  _CASEBRANCH._serialized_end=2045
  _CASEEXPR._serialized_start=2047
  _CASEEXPR._serialized_end=2156
  _INLISTEXPR._serialized_start=2158
  _INLISTEXPR._serialized_end=2260
  _LIKEEXPR._serialized_start=2262
  _LIKEEXPR._serialized_end=2342
  _SCALARFUNCEXPR._serialized_start=2344
  _SCALARFUNCEXPR._serialized_end=2409
  _ALIASEXPR._serialized_start=2411
  _ALIASEXPR._serialized_end=2472
  _HOSTUDFEXPR._serialized_start=2474
  _HOSTUDFEXPR._serialized_end=2545
  _SPARKUDFWRAPPEREXPR._serialized_start=2548
  _SPARKUDFWRAPPEREXPR._serialized_end=2686
  _PHYSICALPLANNODE._serialized_start=2689
  _PHYSICALPLANNODE._serialized_end=4053
  _MEMORYSCANNODE._serialized_start=4055
  _MEMORYSCANNODE._serialized_end=4156
  _PARQUETSCANNODE._serialized_start=4159
  _PARQUETSCANNODE._serialized_end=4449
  _FILERANGES._serialized_start=4451
  _FILERANGES._serialized_end=4494
  _FILEPARTITIONVALUES._serialized_start=4496
  _FILEPARTITIONVALUES._serialized_end=4560
  _PARTITIONVALUES._serialized_start=4562
  _PARTITIONVALUES._serialized_end=4620
  _ORCSCANNODE._serialized_start=4623
  _ORCSCANNODE._serialized_end=4757
  _PROJECTNODE._serialized_start=4759
  _PROJECTNODE._serialized_end=4867
  _FILTERNODE._serialized_start=4870
  _FILTERNODE._serialized_end=5033
  _NAMEDEXPR._serialized_start=5035
  _NAMEDEXPR._serialized_end=5095
  _AGGFUNCTIONPROTO._serialized_start=5097
  _AGGFUNCTIONPROTO._serialized_end=5194
  _BLOOMFILTERAGGNODE._serialized_start=5197
  _BLOOMFILTERAGGNODE._serialized_end=5404
  _AGGNODE._serialized_start=5407
  _AGGNODE._serialized_end=5613
  _SORTFIELDPROTO._serialized_start=5615
  _SORTFIELDPROTO._serialized_end=5706
  _SORTNODE._serialized_start=5709
  _SORTNODE._serialized_end=5840
  _LIMITNODE._serialized_start=5842
  _LIMITNODE._serialized_end=5912
  _UNIONNODE._serialized_start=5914
  _UNIONNODE._serialized_end=5970
  _RENAMECOLUMNSNODE._serialized_start=5972
  _RENAMECOLUMNSNODE._serialized_end=6050
  _EMPTYPARTITIONSNODE._serialized_start=6052
  _EMPTYPARTITIONSNODE._serialized_end=6137
  _DEBUGNODE._serialized_start=6139
  _DEBUGNODE._serialized_end=6224
  _COALESCEBATCHESNODE._serialized_start=6226
  _COALESCEBATCHESNODE._serialized_end=6312
  _PARTITIONINGPROTO._serialized_start=6315
  _PARTITIONINGPROTO._serialized_end=6601
  _PARTITIONINGPROTO_KIND._serialized_start=6545
  _PARTITIONINGPROTO_KIND._serialized_end=6601
  _SHUFFLEWRITERNODE._serialized_start=6604
  _SHUFFLEWRITERNODE._serialized_end=6772
  _RSSSHUFFLEWRITERNODE._serialized_start=6775
  _RSSSHUFFLEWRITERNODE._serialized_end=6935
  _IPCREADERNODE._serialized_start=6937
  _IPCREADERNODE._serialized_end=7050
  _IPCWRITERNODE._serialized_start=7052
  _IPCWRITERNODE._serialized_end=7145
  _BROADCASTJOINNODE._serialized_start=7148
  _BROADCASTJOINNODE._serialized_end=7481
  _BROADCASTJOINBUILDHASHMAPNODE._serialized_start=7483
  _BROADCASTJOINBUILDHASHMAPNODE._serialized_end=7593
  _HASHJOINNODE._serialized_start=7596
  _HASHJOINNODE._serialized_end=7848
  _SORTMERGEJOINNODE._serialized_start=7851
  _SORTMERGEJOINNODE._serialized_end=8103
  _WINDOWFUNCTIONPROTO._serialized_start=8106
  _WINDOWFUNCTIONPROTO._serialized_end=8420
  _WINDOWNODE._serialized_start=8423
  _WINDOWNODE._serialized_end=8618
  _EXPANDPROJECTION._serialized_start=8620
  _EXPANDPROJECTION._serialized_end=8674
  _EXPANDNODE._serialized_start=8676
  _EXPANDNODE._serialized_end=8797
  _GENERATENODE._serialized_start=8800
  _GENERATENODE._serialized_end=9087
  _PARQUETSINKNODE._serialized_start=9089
  _PARQUETSINKNODE._serialized_end=9198
  _OBJECTAGGNODE._serialized_start=9201
  _OBJECTAGGNODE._serialized_end=9358
  _TASKDEFINITION._serialized_start=9360
  _TASKDEFINITION._serialized_end=9473
# @@protoc_insertion_point(module_scope)
